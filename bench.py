"""Benchmark driver.

Default mode measures the BASELINE.json primary metric — FedAvg
wall-clock/round + samples/sec @ 256 simulated clients (MNIST-LR shape, the
reference's ``config/simulation_sp/fedml_config.yaml`` scaled up) — plus MFU
and a single-chip LLM LoRA benchmark (tokens/sec, step time, MFU,
flash-vs-blockwise attention ratio), then prints ONE json line.

``python bench.py --attn`` instead runs the flash-vs-blockwise attention
parity + timing sweep (S in {512, 2048, 4096}, causal x dtype x GQA) and
prints that as one json line.

``python bench.py --serve`` benchmarks the serving plane: KV-cached vs
full-buffer decode, continuous batching vs sequential, and int8 vs full
precision, printing one json line of tokens/sec numbers.

``python bench.py --agg`` times the mesh engine's server-update layouts —
``update_sharding=scatter`` (reduce-scatter merge + shard-resident server
optimizer, docs/UPDATE_SHARDING.md) vs ``replicated`` (full-model psum +
N-way redundant update) — at 256 clients/round on an 8-shard mesh (virtual
CPU devices when no accelerator provides 8), one json line with both
wall-clocks.

``python bench.py --comms`` compares the low-precision collective layer
(``collective_precision`` = fp32 | bf16 | int8, docs/COLLECTIVE_PRECISION.md)
on the 8-shard scatter mesh: steady-state s/round plus the modeled
interconnect bytes/round each precision moves through the merge+broadcast
collectives, one json line.

``python bench.py --mesh2d`` compares the 1-D ``(8, 1)`` vs 2-D ``(4, 2)``
``client × model`` mesh layout (``args.mesh_shape``, docs/MESH_2D.md) at a
fixed 8-chip count — s/round + per-axis modeled interconnect bytes — and
records the LLM_SCALE row the 2-D layout unlocks: the largest model whose
per-chip HBM estimate fits ``(4, 2)`` but exceeds one chip on the 1-D
layout (``core/memory_estimate.py``), one json line.

``python bench.py --pipeline`` compares the 2-D ``(4, 2)`` layout vs the 3-D
``(2, 2, 2)`` ``client × stage × model`` pipeline layout (``args.mesh_shape``,
docs/PIPELINE.md) at a fixed 8-chip count on the layer-stacked ``pipe_mlp``
model — s/round + the three-way per-axis modeled interconnect byte split —
and records the LLM_SCALE row the stage axis unlocks: the estimator-picked
``(c, s, m)`` whose per-chip HBM estimate beats the best ``(c, m)`` at equal
chips for a 98%-staged 1B model (``core/memory_estimate.py``), one json line.

``python bench.py --population`` compares a P-member hyperparameter sweep
run as ONE vmapped-population dispatch (``args.population_axes``,
docs/PRIMITIVES.md) against P sequential single-config runs at P in
{1, 4, 16} — total wall-clock (incl. per-config compile) and steady-state
s/round-per-config, one json line.

``python bench.py --trace`` measures the fedtrace observability plane:
steady-state s/round untraced vs. traced (acceptance: <5% overhead) plus the
``tools/fedtrace.py summarize`` per-phase round breakdown folded into the
json line (docs/OBSERVABILITY.md); FEDML_TRACE_OUT=path keeps the Chrome
trace.

``python bench.py --health`` runs the fedmon federation-health plane
(docs/OBSERVABILITY.md) on a label-flip injection scenario: 10% flipped
clients detected by the robust per-client anomaly detector
(precision/recall pinned), the live /metrics + /healthz endpoint scraped
mid-run with a deliberately violated straggler SLO driving the
ok→degraded transition, and steady-state overhead health-on vs health-off
(acceptance ≤ 3%), one json line.

``vs_baseline``: the reference has no published numbers, so the
ratio is measured against an in-process torch-CPU eager reimplementation of
the reference's client loop (``my_model_trainer_classification.py``
semantics: per-batch zero_grad/forward/backward/step + state_dict FedAvg) on
a subsample, linearly extrapolated.  >1 means fedml_tpu is faster.

Nothing here falls back: a backend that cannot be created, a device with no
entry in ``PEAK_FLOPS`` where a peak is needed, or a phase that raises ends
the run non-zero.  Every json line carries ``platform`` + ``device_kind``.

Timing: each measurement chains device work and ends in a host read-back of
a value that depends on the whole chain (``_timed_chain``), with the
read-back's own latency measured and subtracted.  Replacing this with
``block_until_ready`` windows belongs to the benchmark rebuild (ROADMAP 1.1).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

CLIENTS_PER_ROUND = 256
TOTAL_CLIENTS = 1000
BATCH = 10
STEPS_PER_CLIENT = 6  # 60 samples/client at batch 10, matching MNIST-LR scale
ROUNDS_TIMED = 10
IMG = (28, 28, 1)
NUM_CLASSES = 10

# bf16 peak per chip, by device_kind substring (jax.devices()[0].device_kind).
PEAK_FLOPS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def _peak_flops(device) -> float:
    """Nominal bf16 peak of ``device``; a device that is not in the table is
    an error, never a default and never a rate measured on something else."""
    kind = getattr(device, "device_kind", "").lower()
    if "tpu" in kind:
        for marker, peak in PEAK_FLOPS:
            if marker in kind:
                return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to PEAK_FLOPS with its "
        f"source before reporting an MFU against it")


def _readback(x) -> float:
    """Force a host transfer of (a scalar reduced from) x: returns once
    everything x depends on has run."""
    import jax
    import jax.numpy as jnp
    leaf = jax.tree.leaves(x)[0]
    return float(np.asarray(jnp.sum(leaf.astype(jnp.float32))))


def measure_rtt() -> float:
    """Dispatch+readback latency of a trivial op."""
    import jax.numpy as jnp
    f = lambda: _readback(jnp.zeros((8,)) + 1.0)
    f()
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        f()
    return (time.perf_counter() - t0) / reps


def _timed_chain(run_n_rounds, result_of, min_total_s: float = 2.0,
                 n0: int = 10, rtt: float = 0.0):
    """Time ``run_n_rounds(n)`` (which must chain device work so that
    ``result_of()``'s readback forces all of it), adaptively increasing n
    until total wall-clock >= min_total_s so the read-back amortizes."""
    n = n0
    for _ in range(4):
        t0 = time.perf_counter()
        run_n_rounds(n)
        _ = result_of()
        total = time.perf_counter() - t0
        if total >= min_total_s:
            break
        per = max((total - rtt) / n, 1e-6)
        n = min(int(min_total_s * 1.3 / per) + 1, 2000)
    return max(total - rtt, 1e-9) / n


#: host-context keys every bench mode's JSON carries (one list, three
#: consumers — --serve, --attn, default)
_HOST_CTX_KEYS = ("platform", "device_kind",
                  "host_load_avg_1m", "host_load_avg_5m", "host_cpus")


def _platform_info(measure_peak: bool = True):
    """``measure_peak=False`` is for the modes that report no utilization
    (everything but the default run and ``--llm-ablate``): they run on any
    platform and name it; the other two need a device in ``PEAK_FLOPS``."""
    from fedml_tpu import device as device_mod
    d = device_mod.initialize_backend()[0]
    peak = _peak_flops(d) if measure_peak else None
    # concurrent-load context (round-4 weak #8: CPU numbers swung 3x
    # between rounds with no way to attribute noise — record the host
    # load so cross-round CPU comparisons carry their own caveat)
    try:
        load1, load5, _ = os.getloadavg()
    except OSError:
        load1 = load5 = None
    return {
        "platform": d.platform,
        "device_kind": d.device_kind,
        "peak_flops": peak,
        "peak_flops_source": "nominal_tpu_bf16" if peak else None,
        "host_load_avg_1m": load1,
        "host_load_avg_5m": load5,
        "host_cpus": os.cpu_count(),
    }


def bench_fedml_tpu():
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, device as device_mod, model as model_mod
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    args = load_arguments()
    args.update(
        dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
        train_size=TOTAL_CLIENTS * BATCH * STEPS_PER_CLIENT, test_size=1000,
        model="lr", client_num_in_total=TOTAL_CLIENTS,
        client_num_per_round=CLIENTS_PER_ROUND, comm_round=ROUNDS_TIMED,
        epochs=1, batch_size=BATCH, learning_rate=0.03,
        partition_method="homo", frequency_of_the_test=10 ** 9,
        random_seed=0,
    )
    args = fedml_tpu.init(args, should_init_logs=False)
    dev = device_mod.get_device(args)
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    api = FedAvgAPI(args, dev, dataset, model, client_mode="vmap")

    # warmup (compile)
    api.train_one_round(0)
    api.train_one_round(1)
    _readback(api.state.global_params)
    rtt = measure_rtt()

    rounds_done = [2]

    def run_n(n):
        for _ in range(n):
            api.train_one_round(rounds_done[0])
            rounds_done[0] += 1

    return _timed_chain(run_n, lambda: _readback(api.state.global_params),
                        n0=ROUNDS_TIMED, rtt=rtt)


def fedavg_round_flops() -> float:
    """Model FLOPs of one FedAvg round: per SGD step on the LR model the
    forward is one (B,D)x(D,C) matmul (2BDC) and the backward two (4BDC)."""
    d = int(np.prod(IMG))
    per_step = 6.0 * BATCH * d * NUM_CLASSES
    return CLIENTS_PER_ROUND * STEPS_PER_CLIENT * per_step


def bench_torch_reference_style(n_clients: int = 8) -> float:
    """Reference-style eager loop (torch CPU), per-round time extrapolated to
    CLIENTS_PER_ROUND.  Mirrors the hot path of
    ``ml/trainer/my_model_trainer_classification.py`` + per-key FedAvg
    (``ml/aggregator/agg_operator.py:33``)."""
    import torch
    import torch.nn as nn

    torch.set_num_threads(max(1, (torch.get_num_threads() or 4)))
    dim = int(np.prod(IMG))
    xs = torch.randn(n_clients, STEPS_PER_CLIENT, BATCH, dim)
    ys = torch.randint(0, NUM_CLASSES, (n_clients, STEPS_PER_CLIENT, BATCH))

    def one_round():
        global_sd = nn.Linear(dim, NUM_CLASSES).state_dict()
        locals_ = []
        for c in range(n_clients):
            m = nn.Linear(dim, NUM_CLASSES)
            m.load_state_dict(global_sd)
            opt = torch.optim.SGD(m.parameters(), lr=0.03, weight_decay=1e-3)
            crit = nn.CrossEntropyLoss()
            for s in range(STEPS_PER_CLIENT):
                opt.zero_grad()
                loss = crit(m(xs[c, s]), ys[c, s])
                loss.backward()
                opt.step()
            locals_.append((BATCH * STEPS_PER_CLIENT, m.state_dict()))
        # per-key weighted average (reference agg loop)
        total = sum(n for n, _ in locals_)
        avg = {k: sum(sd[k] * (n / total) for n, sd in locals_)
               for k in locals_[0][1]}
        return avg

    one_round()  # warmup
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        one_round()
    per_round = (time.perf_counter() - t0) / reps
    return per_round * (CLIENTS_PER_ROUND / n_clients)


# -- server-update sharding benchmark (--agg) --------------------------------
def bench_update_sharding(rounds: int | None = None,
                          clients_per_round: int | None = None) -> dict:
    """scatter vs replicated server-update wall-clock on the mesh engine,
    same cohort/seed/model for both layouts.  FedOpt is the representative
    algorithm: its Adam step is the heaviest stage-2 the zoo has, so it
    exposes the per-chip 1/n_shards update win the scatter layout buys.
    FEDML_AGG_QUICK=1 shrinks the cohort for smoke tests."""
    import jax

    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    quick = os.environ.get("FEDML_AGG_QUICK") == "1"
    cpr = clients_per_round or (16 if quick else CLIENTS_PER_ROUND)
    total = max(4 * cpr, 64) if quick else TOTAL_CLIENTS
    timed_rounds = rounds or (2 if quick else ROUNDS_TIMED)
    rtt = None
    out = {"clients_per_round": cpr, "quick": quick}

    for mode in ("scatter", "replicated"):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total * BATCH * STEPS_PER_CLIENT, test_size=256,
            model="lr", client_num_in_total=total,
            client_num_per_round=cpr, comm_round=timed_rounds + 2,
            epochs=1, batch_size=BATCH, learning_rate=0.03,
            partition_method="homo", frequency_of_the_test=10 ** 9,
            random_seed=0, federated_optimizer="FedOpt",
            update_sharding=mode,
        )
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        api = MeshFedAvgAPI(args, None, dataset, model)
        out["n_shards"] = api.n_shards
        api.train_one_round(0)  # compile
        api.train_one_round(1)
        _readback(api.state.global_params)
        if rtt is None:
            rtt = measure_rtt()
        rounds_done = [2]

        def run_n(n):
            for _ in range(n):
                api.train_one_round(rounds_done[0] % args.comm_round)
                rounds_done[0] += 1

        dt = _timed_chain(run_n,
                          lambda: _readback(api.state.global_params),
                          min_total_s=0.5 if quick else 2.0,
                          n0=timed_rounds, rtt=rtt)
        out[f"{mode}_s_per_round"] = round(dt, 5)
    out["scatter_speedup"] = round(
        out["replicated_s_per_round"] / out["scatter_s_per_round"], 3)
    return out


# -- low-precision collective benchmark (--comms) ----------------------------
def bench_comms(rounds: int | None = None,
                clients_per_round: int | None = None) -> dict:
    """--comms: the low-precision collective layer
    (``args.collective_precision``, docs/COLLECTIVE_PRECISION.md) on the
    8-shard scatter mesh at 256 clients/round: steady-state s/round AND the
    modeled interconnect payload bytes/round of the merge+broadcast
    collectives at each precision.  The byte numbers are read back from the
    round's own device-carried ObsCarry record (the same field ``fedtrace
    summarize`` reports), so the bench exercises the real plumbing rather
    than re-deriving the model host-side.  FEDML_COMMS_QUICK=1 shrinks the
    cohort for smoke tests."""
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    quick = os.environ.get("FEDML_COMMS_QUICK") == "1"
    cpr = clients_per_round or (16 if quick else CLIENTS_PER_ROUND)
    total = max(4 * cpr, 64) if quick else TOTAL_CLIENTS
    timed_rounds = rounds or (2 if quick else ROUNDS_TIMED)
    rtt = None
    out = {"clients_per_round": cpr, "quick": quick,
           "update_sharding": "scatter"}

    for precision in ("fp32", "bf16", "int8"):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total * BATCH * STEPS_PER_CLIENT, test_size=256,
            model="lr", client_num_in_total=total,
            client_num_per_round=cpr, comm_round=timed_rounds + 2,
            epochs=1, batch_size=BATCH, learning_rate=0.03,
            partition_method="homo", frequency_of_the_test=10 ** 9,
            random_seed=0, update_sharding="scatter",
            collective_precision=precision,
        )
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        api = MeshFedAvgAPI(args, None, dataset, model)
        out["n_shards"] = api.n_shards
        metrics = api.train_one_round(0)  # compile
        # device-carried modeled bytes (trace-time static, so round 0's
        # record is the steady-state value)
        out[f"{precision}_bytes_per_round"] = int(
            np.asarray(metrics["obs"].collective_bytes))
        out[f"{precision}_quant_error_norm"] = round(float(
            np.asarray(metrics["obs"].quant_error_norm)), 6)
        api.train_one_round(1)
        _readback(api.state.global_params)
        if rtt is None:
            rtt = measure_rtt()
        rounds_done = [2]

        def run_n(n):
            for _ in range(n):
                api.train_one_round(rounds_done[0] % args.comm_round)
                rounds_done[0] += 1

        dt = _timed_chain(run_n,
                          lambda: _readback(api.state.global_params),
                          min_total_s=0.5 if quick else 2.0,
                          n0=timed_rounds, rtt=rtt)
        out[f"{precision}_s_per_round"] = round(dt, 5)
    for precision in ("bf16", "int8"):
        out[f"{precision}_bytes_reduction"] = round(
            out["fp32_bytes_per_round"]
            / out[f"{precision}_bytes_per_round"], 3)
        out[f"{precision}_round_slowdown"] = round(
            out[f"{precision}_s_per_round"] / out["fp32_s_per_round"], 3)
    return out


# -- 2-D client × model mesh benchmark (--mesh2d) ----------------------------
def bench_verify() -> dict:
    """--verify: the fedverify census as a BENCH row (ISSUE 10,
    docs/FEDVERIFY.md) — every canonical program AOT-lowers + compiles
    on the host and the row records, per program, the compiled
    collective census (count/kind/axis), the payload bytes it moves per
    round next to the ObsCarry model's prediction, the per-chip
    argument+temp HBM footprint against the estimator's bound, and the
    distinct-signature (recompile-surface) count; plus the headline
    ``violations`` (unsuppressed contract failures — the tier-1 gate
    pins this at 0).  No step executes: the whole row is static
    analysis of what XLA compiles.  FEDML_VERIFY_QUICK=1 restricts to
    the three cheapest programs for smoke tests."""
    from fedml_tpu.analysis import fedverify as fv
    from fedml_tpu.analysis import programs as program_registry

    quick = os.environ.get("FEDML_VERIFY_QUICK") == "1"
    names = program_registry.names(quick=True) if quick else None
    findings, reports = fv.verify_programs(names)
    active = [f for f in findings if not f.suppressed]
    out = {"quick": quick, "violations": len(active),
           "suppressed": sum(1 for f in findings if f.suppressed),
           "programs": {}}
    for rep in reports:
        out["programs"][rep.name] = {
            "collectives": rep.collective_counts(),
            "census_bytes": {k: round(v) for k, v in
                             rep.census_bytes().items()},
            "modeled_bytes": {k: round(v) for k, v in
                              rep.modeled_bytes.items() if v},
            "hbm_per_chip": round(rep.per_chip_total()),
            "hbm_estimate": round(rep.estimate_bytes),
            "distinct_signatures": len(set(rep.signatures)),
            "num_partitions": rep.num_partitions,
        }
    if active:
        out["violation_lines"] = [
            f"{f.path}: {f.rule}: {f.message}" for f in active]
    return out


def bench_mesh2d(rounds: int | None = None,
                 clients_per_round: int | None = None) -> dict:
    """--mesh2d: the 1-D ``(8, 1)`` vs 2-D ``(4, 2)`` layout
    (``args.mesh_shape``, docs/MESH_2D.md) at a FIXED 8-chip count:
    steady-state s/round plus the per-axis modeled interconnect bytes the
    round carries in its own ObsCarry record (``collective_bytes_client``
    vs ``collective_bytes_model``), and final-round losses so layout
    parity is visible in the json line.

    The LLM_SCALE row is the scale unlock itself: using
    ``core.memory_estimate.estimate_mesh_state_memory`` it picks the
    largest candidate model whose per-chip HBM estimate fits the 2-D
    layout on a v5e chip, and records that the SAME model exceeds one
    chip on the 1-D layout — the config the 1-D mesh cannot run at all.
    FEDML_MESH2D_QUICK=1 shrinks the cohort for smoke tests."""
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.core.memory_estimate import (
        GIB, HBM_PER_CHIP, MeshStateLayout, estimate_mesh_state_memory,
        largest_runnable_params)
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    quick = os.environ.get("FEDML_MESH2D_QUICK") == "1"
    cpr = clients_per_round or (16 if quick else CLIENTS_PER_ROUND)
    total = max(4 * cpr, 64) if quick else TOTAL_CLIENTS
    timed_rounds = rounds or (2 if quick else ROUNDS_TIMED)
    rtt = None
    out = {"clients_per_round": cpr, "quick": quick,
           "update_sharding": "scatter"}

    for label, shape in (("mesh1d", "8,1"), ("mesh2d", "4,2")):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total * BATCH * STEPS_PER_CLIENT, test_size=256,
            model="lr", client_num_in_total=total,
            client_num_per_round=cpr, comm_round=timed_rounds + 2,
            epochs=1, batch_size=BATCH, learning_rate=0.03,
            partition_method="homo", frequency_of_the_test=10 ** 9,
            random_seed=0, federated_optimizer="FedOpt",
            # toy-default server_lr=1.0 drives the synthetic LR task to a
            # saturated (loss-underflow) optimum in one round; 0.03 keeps
            # the curve informative so layout parity is visible in the row
            server_lr=0.03,
            update_sharding="scatter", mesh_shape=shape,
        )
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        api = MeshFedAvgAPI(args, None, dataset, model)
        out[f"{label}_shape"] = [api.n_shards, api.n_model_shards]
        metrics = api.train_one_round(0)  # compile
        # per-axis modeled bytes from the round's own ObsCarry record
        # (trace-time static, so round 0's value is steady-state)
        obs = metrics["obs"]
        out[f"{label}_client_bytes_per_round"] = int(
            np.asarray(obs.collective_bytes_client))
        out[f"{label}_model_bytes_per_round"] = int(
            np.asarray(obs.collective_bytes_model))
        m2 = api.train_one_round(1)
        out[f"{label}_round1_loss"] = round(float(
            np.asarray(m2["train_loss"])), 6)
        _readback(api.state.global_params)
        if rtt is None:
            rtt = measure_rtt()
        rounds_done = [2]

        def run_n(n):
            for _ in range(n):
                api.train_one_round(rounds_done[0] % args.comm_round)
                rounds_done[0] += 1

        dt = _timed_chain(run_n,
                          lambda: _readback(api.state.global_params),
                          min_total_s=0.5 if quick else 2.0,
                          n0=timed_rounds, rtt=rtt)
        out[f"{label}_s_per_round"] = round(dt, 5)
    out["mesh2d_vs_1d_round"] = round(
        out["mesh1d_s_per_round"] / out["mesh2d_s_per_round"], 3)

    # -- LLM_SCALE row: the model the 2-D layout unlocks ---------------------
    # scan the 8-chip mesh factorizations for the largest candidate model
    # whose per-chip estimate fits a v5e, then record that the winning
    # config exceeds one chip on the 1-D (8, 1) layout — the model the
    # 1-D mesh cannot run at all (ISSUE 6 acceptance; the 1.075B
    # BASELINE flagship sits exactly in this band)
    chip = "v5e"
    budget = HBM_PER_CHIP[chip]
    est_kw = dict(clients_per_round=8, algorithm="fedopt",
                  collective_precision="int8", param_bytes=2)
    candidates = [0.25e9, 0.5e9, 0.75e9, 1.075e9, 1.5e9, 2e9, 3e9, 6.74e9]
    shapes = [(8, 1), (4, 2), (2, 4), (1, 8)]
    per_shape = {s: largest_runnable_params(budget, s, candidates, **est_kw)
                 for s in shapes}
    best = max((s for s in shapes if s[1] > 1),
               key=lambda s: (per_shape[s], s[0]))
    n = per_shape[best]
    est2 = estimate_mesh_state_memory(
        MeshStateLayout(n_params=n, mesh_shape=best, **est_kw))
    est1 = estimate_mesh_state_memory(
        MeshStateLayout(n_params=n, mesh_shape=(8, 1), **est_kw))
    out["llm_scale"] = {
        "chip": chip, "hbm_per_chip_gib": round(budget / GIB, 2),
        "n_params": n,
        "mesh_shape": list(best),
        "largest_runnable_b_by_shape": {
            f"{c}x{m}": round(per_shape[(c, m)] / 1e9, 3)
            for c, m in shapes},
        "mesh1d_per_chip_gib": round(est1["total_gib"], 2),
        "mesh1d_fits": est1["total"] <= budget,
        "mesh2d_per_chip_gib": round(est2["total_gib"], 2),
        "mesh2d_fits": est2["total"] <= budget,
    }
    return out


# -- 3-D pipeline benchmark (--pipeline) -------------------------------------
def bench_pipeline(rounds: int | None = None,
                   clients_per_round: int | None = None) -> dict:
    """--pipeline: the 2-D ``(4, 2)`` client × model layout vs the 3-D
    ``(2, 2, 2)`` client × stage × model pipeline layout
    (``args.mesh_shape``, docs/PIPELINE.md) at a FIXED 8-chip count on
    the layer-stacked ``pipe_mlp`` model: steady-state s/round plus the
    per-axis modeled interconnect bytes each round carries in its own
    ObsCarry record (``collective_bytes_client`` /
    ``collective_bytes_stage`` / ``collective_bytes_model``), and
    round-1 losses so layout parity is visible in the json line.
    Stage-axis traffic — the microbatched ppermute ring — exists exactly
    on the 3-D layout; the client-axis merge payload stays
    layout-independent.

    The LLM_SCALE row is the scale unlock itself: for a model that is
    almost entirely stage-partitionable (``stage_fraction=0.98``) and
    whose model-axis efficiency saturates at 4 shards
    (``max_model_parallel=4``, docs/PIPELINE.md byte model), the
    estimator scans every 8-chip ``(c, s, m)`` factorization and picks
    the one whose per-chip HBM estimate beats the BEST 2-D ``(c, m)``
    layout at EQUAL chips — the headroom fedverify's HBM family confirms
    upper-bounds the real lowering (ISSUE 18 acceptance).
    FEDML_PIPE_QUICK=1 shrinks the cohort for smoke tests."""
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.core.memory_estimate import (
        GIB, HBM_PER_CHIP, MeshStateLayout, estimate_mesh_state_memory)
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    quick = os.environ.get("FEDML_PIPE_QUICK") == "1"
    cpr = clients_per_round or (16 if quick else CLIENTS_PER_ROUND)
    total = max(4 * cpr, 64) if quick else TOTAL_CLIENTS
    timed_rounds = rounds or (2 if quick else ROUNDS_TIMED)
    rtt = None
    out = {"clients_per_round": cpr, "quick": quick,
           "update_sharding": "scatter", "model": "pipe_mlp",
           "microbatches": 5}

    # microbatches only splits the batch on the pipeline layout; the 2-D
    # run keeps the un-split batch (same per-step gradient either way —
    # equal microbatches preserve the mean)
    for label, shape, micro in (("mesh2d", "4,2", 1),
                                ("mesh3d", "2,2,2", 5)):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total * BATCH * STEPS_PER_CLIENT, test_size=256,
            model="pipe_mlp", model_dim=32, model_layers=4,
            client_num_in_total=total,
            client_num_per_round=cpr, comm_round=timed_rounds + 2,
            epochs=1, batch_size=BATCH, learning_rate=0.03,
            partition_method="homo", frequency_of_the_test=10 ** 9,
            random_seed=0, federated_optimizer="FedOpt",
            # same rationale as --mesh2d: toy-default server_lr saturates
            # the synthetic task in one round; 0.03 keeps parity visible
            server_lr=0.03,
            update_sharding="scatter", mesh_shape=shape,
            microbatches=micro,
        )
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        api = MeshFedAvgAPI(args, None, dataset, model)
        out[f"{label}_shape"] = [api.n_shards, api.n_stage_shards,
                                 api.n_model_shards]
        metrics = api.train_one_round(0)  # compile
        # per-axis modeled bytes from the round's own ObsCarry record
        # (trace-time static, so round 0's value is steady-state)
        obs = metrics["obs"]
        out[f"{label}_client_bytes_per_round"] = int(
            np.asarray(obs.collective_bytes_client))
        out[f"{label}_stage_bytes_per_round"] = int(
            np.asarray(obs.collective_bytes_stage))
        out[f"{label}_model_bytes_per_round"] = int(
            np.asarray(obs.collective_bytes_model))
        m2 = api.train_one_round(1)
        out[f"{label}_round1_loss"] = round(float(
            np.asarray(m2["train_loss"])), 6)
        _readback(api.state.global_params)
        if rtt is None:
            rtt = measure_rtt()
        rounds_done = [2]

        def run_n(n):
            for _ in range(n):
                api.train_one_round(rounds_done[0] % args.comm_round)
                rounds_done[0] += 1

        dt = _timed_chain(run_n,
                          lambda: _readback(api.state.global_params),
                          min_total_s=0.5 if quick else 2.0,
                          n0=timed_rounds, rtt=rtt)
        out[f"{label}_s_per_round"] = round(dt, 5)
    out["mesh3d_vs_2d_round"] = round(
        out["mesh2d_s_per_round"] / out["mesh3d_s_per_round"], 3)

    # -- LLM_SCALE row: the layout the stage axis unlocks --------------------
    # at 1B params with a 98%-staged model and model-parallel efficiency
    # capped at 4 shards, the best 2-D factorization can only divide the
    # staged plane by eff_model <= 4; adding the stage axis divides it by
    # eff_stage * eff_model, so the estimator-picked (c, s, m) lands
    # under the best (c, m) per-chip total at the SAME 8 chips
    chip = "v5e"
    budget = HBM_PER_CHIP[chip]
    est_kw = dict(clients_per_round=8, algorithm="fedopt",
                  collective_precision="int8", param_bytes=2,
                  stage_fraction=0.98, max_model_parallel=4)
    n = 1.0e9
    shapes2d = [(8, 1), (4, 2), (2, 4), (1, 8)]
    shapes3d = [(2, 2, 2), (1, 2, 4), (1, 4, 2),
                (2, 4, 1), (4, 2, 1), (1, 8, 1)]

    def per_chip(shape):
        return estimate_mesh_state_memory(
            MeshStateLayout(n_params=n, mesh_shape=shape, **est_kw))

    est2 = {s: per_chip(s) for s in shapes2d}
    est3 = {s: per_chip(s) for s in shapes3d}
    best2 = min(shapes2d, key=lambda s: (est2[s]["total"], s))
    best3 = min(shapes3d, key=lambda s: (est3[s]["total"], s))
    out["llm_scale"] = {
        "chip": chip, "hbm_per_chip_gib": round(budget / GIB, 2),
        "n_params": n,
        "stage_fraction": est_kw["stage_fraction"],
        "max_model_parallel": est_kw["max_model_parallel"],
        "mesh2d_shape": list(best2),
        "mesh3d_shape": list(best3),
        "per_chip_gib_by_shape": {
            "x".join(str(d) for d in s): round(e["total_gib"], 3)
            for s, e in list(est2.items()) + list(est3.items())},
        "mesh2d_per_chip_gib": round(est2[best2]["total_gib"], 2),
        "mesh3d_per_chip_gib": round(est3[best3]["total_gib"], 2),
        "mesh2d_fits": est2[best2]["total"] <= budget,
        "mesh3d_fits": est3[best3]["total"] <= budget,
        "mesh3d_vs_2d_per_chip": round(
            est3[best3]["total"] / est2[best2]["total"], 4),
    }
    return out


# -- round-block fusion benchmark (--fused) ----------------------------------
def bench_round_fusion(rounds: int | None = None,
                       clients_per_round: int | None = None,
                       block: int = 8) -> dict:
    """Fused round-block (``args.round_block``) vs per-round dispatch on the
    SP engine: steady-state s/round at K=1 and K=``block`` on the 256-client
    MNIST-LR config.  K=1 runs the normal ``train_one_round`` loop (per-round
    staging + dispatch); K=``block`` runs ``train_block`` (one compiled
    ``lax.scan`` over K rounds, cohorts for the next block staged on the
    worker thread).  FEDML_FUSED_QUICK=1 shrinks the cohort for smoke
    tests."""
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    quick = os.environ.get("FEDML_FUSED_QUICK") == "1"
    cpr = clients_per_round or (16 if quick else CLIENTS_PER_ROUND)
    total = max(4 * cpr, 64) if quick else TOTAL_CLIENTS
    timed_rounds = rounds or (2 * block if quick else 5 * block)
    rtt = None
    out = {"clients_per_round": cpr, "round_block": block, "quick": quick}

    for k in (1, block):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total * BATCH * STEPS_PER_CLIENT, test_size=256,
            model="lr", client_num_in_total=total,
            client_num_per_round=cpr,
            # comm_round only clamps the ragged tail; sampling/staging are
            # pure functions of round_idx, so steady-state blocks can run
            # at any start index
            comm_round=10 ** 6,
            epochs=1, batch_size=BATCH, learning_rate=0.03,
            partition_method="homo", frequency_of_the_test=10 ** 9,
            random_seed=0, round_block=k,
        )
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        api = FedAvgAPI(args, None, dataset, model, client_mode="vmap")

        rounds_done = [0]

        def run_rounds(n):
            if k == 1:
                for _ in range(n):
                    api.train_one_round(rounds_done[0])
                    rounds_done[0] += 1
            else:
                done = 0
                while done < n:
                    kk, _ = api.train_block(rounds_done[0])
                    rounds_done[0] += kk
                    done += kk

        run_rounds(2 * k)  # compile + warm
        _readback(api.state.global_params)
        if rtt is None:
            rtt = measure_rtt()
        dt = _timed_chain(run_rounds,
                          lambda: _readback(api.state.global_params),
                          min_total_s=0.5 if quick else 2.0,
                          n0=timed_rounds, rtt=rtt)
        out["fused_s_per_round" if k > 1 else "unfused_s_per_round"] = \
            round(dt, 5)
    out["fused_speedup"] = round(
        out["unfused_s_per_round"] / out["fused_s_per_round"], 3)
    return out


# -- vmapped experiment populations (--population) ---------------------------
def bench_population(rounds: int | None = None,
                     clients_per_round: int | None = None,
                     sizes=(1, 4, 16)) -> dict:
    """--population: a whole hyperparameter sweep as ONE fused dispatch
    (``args.population_axes``, docs/PRIMITIVES.md) vs the same sweep as P
    sequential runs, on the 256-client MNIST-LR config.

    For each P the population path builds ONE api whose round is the
    ``vmap``-over-members program (one compile, one staging stream) and
    times a full cold run — construction + compile + ``timed_rounds``
    rounds; the sequential path builds P single-config apis (one per
    member's client_lr) and runs each the same way, summing their
    wall-clocks.  Total wall-clock is the honest comparison: the per-config
    compile and staging the population amortizes IS the cost a sweep pays.
    Steady-state s/round-per-config is also reported (compile excluded).
    FEDML_POPULATION_QUICK=1 shrinks the cohort + sizes for smoke tests."""
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    quick = os.environ.get("FEDML_POPULATION_QUICK") == "1"
    cpr = clients_per_round or (16 if quick else CLIENTS_PER_ROUND)
    total = max(4 * cpr, 64) if quick else TOTAL_CLIENTS
    timed_rounds = rounds or (3 if quick else ROUNDS_TIMED)
    sizes = (1, 2) if quick else tuple(sizes)
    out = {"clients_per_round": cpr, "rounds": timed_rounds,
           "sizes": list(sizes), "quick": quick}

    def member_lrs(p):
        # distinct member configs: a client-lr grid around the default
        return [round(0.02 + 0.03 * i / max(p - 1, 1), 5) for i in range(p)]

    def make_api(axes, lr=0.03):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total * BATCH * STEPS_PER_CLIENT, test_size=256,
            model="lr", client_num_in_total=total, client_num_per_round=cpr,
            comm_round=10 ** 6, epochs=1, batch_size=BATCH,
            learning_rate=lr, partition_method="homo",
            frequency_of_the_test=10 ** 9, random_seed=0)
        if axes is not None:
            args.update(population_axes=axes)
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        return FedAvgAPI(args, None, dataset, model, client_mode="vmap")

    def cold_run(axes, lr=0.03):
        """Construction + compile + timed_rounds rounds, wall-clock."""
        t0 = time.time()
        api = make_api(axes, lr)
        for r in range(timed_rounds):
            api.train_one_round(r)
        _readback(api.state.global_params)
        return time.time() - t0, api

    rtt = measure_rtt() if not quick else 0.0
    # one throwaway cold run so process-wide first-touch costs (data gen,
    # import, XLA warmup) don't land on whichever variant runs first
    warm_s, warm_api = cold_run(None)
    out["warmup_s"] = round(warm_s, 3)
    del warm_api
    for p in sizes:
        lrs = member_lrs(p)
        # population: ONE api, one compiled vmapped round for all members
        pop_s, api = cold_run({"client_lr": lrs} if p > 1 else None)
        rounds_done = [timed_rounds]

        def run_rounds(n):
            for _ in range(n):
                api.train_one_round(rounds_done[0])
                rounds_done[0] += 1

        steady = _timed_chain(run_rounds,
                              lambda: _readback(api.state.global_params),
                              min_total_s=0.5 if quick else 2.0,
                              n0=timed_rounds, rtt=rtt)
        # sequential: P fresh apis, one per member config — each pays its
        # own construction, compile and staging stream
        seq_s = 0.0
        for lr in lrs:
            dt, seq_api = cold_run(None, lr=lr)
            seq_s += dt
            del seq_api
        out[f"p{p}_pop_wallclock_s"] = round(pop_s, 3)
        out[f"p{p}_seq_wallclock_s"] = round(seq_s, 3)
        out[f"p{p}_pop_vs_seq"] = round(pop_s / seq_s, 3)
        out[f"p{p}_steady_s_per_round"] = round(steady, 5)
        out[f"p{p}_steady_s_per_round_per_config"] = round(steady / p, 5)
        del api
    largest = max(sizes)
    out["value_pop_vs_seq_p%d" % largest] = out[f"p{largest}_pop_vs_seq"]
    return out


# -- paged client-state store benchmark (--store) ----------------------------
def _rss_mb() -> float:
    """Current (not peak) resident set of this process in MiB."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except OSError:
        pass
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def bench_store(rounds: int | None = None) -> dict:
    """--store: the paged million-client state plane (fedml_tpu/store,
    docs/CLIENT_STORE.md) vs today's dense device client table.

    Two SCAFFOLD configs with EQUAL per-round work (same total client
    steps, same samples/round): the dense baseline (small registered
    population, dense device table, 256-client cohorts of 8 steps) and
    the store row (1M registered client ids — an id space whose DENSE
    table cannot be allocated at all — paged sparse host store, 2k-client
    cohorts of 1 step).  Reports steady-state s/round, the host-RSS delta
    across each run, the store's actual resident bytes, the modeled dense
    table bytes at 1M registered, and steady-state recompile counts
    (pinned 0).  FEDML_STORE_QUICK=1 shrinks everything for the tier-1
    smoke."""
    import gc

    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    quick = os.environ.get("FEDML_STORE_QUICK") == "1"
    registered = 50_000 if quick else 1_000_000
    # three configs, equal samples/round throughout: the ANCHOR (today's
    # dense-table config: small cohort, more steps each), a SAME-SHAPE
    # dense run (big cohort, 1 step — isolates the cohort-shape effect),
    # and the STORE row (same shape as the second, but the id space is
    # `registered` and the state plane is the paged store — the delta vs
    # same-shape dense is the true cost of paging)
    dense_cohort, dense_steps = (32, 4) if quick else (256, 8)
    store_cohort = dense_cohort * dense_steps
    timed_rounds = rounds or (3 if quick else ROUNDS_TIMED)

    def make_api(over):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            test_size=256, model="lr", comm_round=10 ** 6, epochs=1,
            batch_size=BATCH, learning_rate=0.1, partition_method="homo",
            federated_optimizer="SCAFFOLD",
            frequency_of_the_test=10 ** 9, random_seed=0)
        args.update(**over)
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        return FedAvgAPI(args, None, dataset, model, client_mode="vmap")

    def run_config(over):
        gc.collect()
        rss0 = _rss_mb()
        api = make_api(over)
        for r in range(2):                      # compile + warm
            api.train_one_round(r)
        _readback(api.state.global_params)
        with JaxRuntimeAudit() as audit:
            t0 = time.time()
            for r in range(2, 2 + timed_rounds):
                api.train_one_round(r)
            _readback(api.state.global_params)
            dt = (time.time() - t0) / timed_rounds
        rss1 = _rss_mb()
        return api, dt, rss1 - rss0, audit.compilations

    anchor_over = dict(
        client_num_in_total=dense_cohort, client_num_per_round=dense_cohort,
        train_size=dense_cohort * dense_steps * BATCH)
    api_a, anchor_s, anchor_rss, anchor_compiles = run_config(anchor_over)
    del api_a
    shape_over = dict(
        client_num_in_total=store_cohort, client_num_per_round=store_cohort,
        train_size=store_cohort * BATCH)
    api_d, shape_s, shape_rss, shape_compiles = run_config(shape_over)
    del api_d
    store_over = dict(shape_over, client_store=True,
                      registered_clients=registered, store_page_size=512)
    api_s, store_s, store_rss, store_compiles = run_config(store_over)
    stats = api_s._pager.stats()
    # LRU cap + spill: the RSS-FLAT configuration — resident rows bounded
    # at max_pages * page_size no matter how many clients build history;
    # finer pages keep the random repeat-id reloads cheap
    import tempfile
    spill = tempfile.mkdtemp(prefix="fedstore_bench_")
    capped_over = dict(shape_over, client_store=True,
                       registered_clients=registered,
                       store_page_size=64 if quick else 128,
                       store_max_pages=8 if quick else 96,
                       store_spill_dir=spill)
    api_c, capped_s, capped_rss, capped_compiles = run_config(capped_over)
    cstats = api_c._pager.stats()
    del api_c
    out = {
        "quick": quick, "rounds": timed_rounds,
        "registered_clients": registered,
        "anchor_cohort": dense_cohort,
        "anchor_steps_per_client": dense_steps,
        "store_cohort": store_cohort, "store_steps_per_client": 1,
        "anchor_dense_s_per_round": round(anchor_s, 5),
        "sameshape_dense_s_per_round": round(shape_s, 5),
        "store_s_per_round": round(store_s, 5),
        # the acceptance ratio: 1M-registered store round vs today's
        # 256-client dense config at equal samples/round
        "store_vs_anchor_round": round(store_s / anchor_s, 3),
        # the isolated state-plane cost: identical cohort shape, dense
        # device table vs paged host store
        "store_vs_dense_sameshape": round(store_s / shape_s, 3),
        "anchor_rss_delta_mb": round(anchor_rss, 1),
        "sameshape_rss_delta_mb": round(shape_rss, 1),
        "store_rss_delta_mb": round(store_rss, 1),
        "store_resident_mb": round(stats["resident_bytes"] / 2 ** 20, 2),
        "store_touched_rows": stats["touched_rows"],
        "store_page_hit_rate": round(stats["page_hit_rate"], 4),
        # the RSS-flat row: LRU cap + spill bounds residency for ANY
        # horizon at the cost of spill I/O on the overlapped threads
        "capped_s_per_round": round(capped_s, 5),
        "capped_vs_dense_sameshape": round(capped_s / shape_s, 3),
        "capped_resident_mb": round(cstats["resident_bytes"] / 2 ** 20, 2),
        "capped_spills": cstats["spills"],
        "capped_loads": cstats["loads"],
        "steady_compiles_capped": capped_compiles,
        # the allocation the dense table would need at this population —
        # the number that cannot exist on the host
        "dense_table_at_registered_gib": round(
            api_s._store.dense_nbytes() / 2 ** 30, 2),
        "steady_compiles_anchor": anchor_compiles,
        "steady_compiles_sameshape": shape_compiles,
        "steady_compiles_store": store_compiles,
    }
    del api_s
    return out


def bench_async(max_rounds: int | None = None) -> dict:
    """--async: buffered-async fedbuff vs sync FedAvg under a
    heavy-tailed client-latency distribution (docs/ASYNC.md).

    Equal samples per aggregation: both engines run the same cohorts
    (same seed → same sampling/staging/rng), C clients × the same local
    steps; one fedbuff buffer apply consumes K = C updates, one sync
    round consumes its lockstep cohort.  The wall-clock axis is the
    VIRTUAL clock of the shared arrival model (simulation/async_sim.py —
    log-normal latency, sigma 1.6, persistent stragglers): a sync round
    costs the MAX of its cohort's latency draws (the straggler gates the
    lockstep), while fedbuff's applies advance at arrival rate with
    staleness-discounted mixing.  Headline: sim-wall-clock to the target
    test accuracy, plus rounds/applies-to-target, the staleness
    envelope, and the JaxRuntimeAudit steady-state recompile pin (0 —
    buffer occupancy/staleness are traced data).
    FEDML_ASYNC_QUICK=1 shrinks everything for the tier-1 smoke."""
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    from fedml_tpu.simulation.async_engine import FedBuffAPI
    from fedml_tpu.simulation.async_sim import ArrivalSimulator
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    quick = os.environ.get("FEDML_ASYNC_QUICK") == "1"
    cohort = 8 if quick else 32
    total_clients = 64 if quick else 256
    rounds_cap = max_rounds or (12 if quick else 80)
    # full mode slows the optimizer so the to-target trajectory spans
    # ~17 sync rounds (measured) — enough straggler-gated rounds for the
    # wall-clock comparison to mean something; quick mode keeps the fast
    # lr so the tier-1 smoke stays cheap
    target_acc = 0.55 if quick else 0.95
    lr = 0.1 if quick else 0.003
    lat = dict(latency_median_s=5.0, latency_sigma=1.6, speed_sigma=0.5)

    def make_args(**over):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total_clients * 40, test_size=512, model="lr",
            client_num_in_total=total_clients,
            client_num_per_round=cohort, comm_round=rounds_cap,
            epochs=1, batch_size=BATCH, learning_rate=lr,
            partition_method="hetero", partition_alpha=0.3,
            frequency_of_the_test=10 ** 9, random_seed=0)
        args.update(**over)
        return fedml_tpu.init(args, should_init_logs=False)

    def make_api(cls, **over):
        args = make_args(**over)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        return cls(args, None, dataset, model)

    # -- sync FedAvg: lockstep rounds gated by the cohort max latency ----
    sync = make_api(FedAvgAPI, federated_optimizer="FedAvg")
    lat_model = ArrivalSimulator(seed=0, **lat)
    sync_clock = 0.0
    sync_rounds = sync_to_target = None
    sync_accs = []
    t0 = time.time()
    for r in range(rounds_cap):
        sync.train_one_round(r)
        draws, _ = lat_model.draw_latencies(
            r, sync._client_sampling(r))
        sync_clock += float(np.max(draws))   # the straggler gates the round
        _, acc = sync.evaluate()
        sync_accs.append(round(float(acc), 4))
        if acc >= target_acc:
            sync_rounds, sync_to_target = r + 1, sync_clock
            break
    sync_host_s = time.time() - t0
    del sync

    # -- fedbuff: event-driven applies over the SAME latency model -------
    # concurrency = inflight_gens × cohort: under a heavy tail the
    # pipeline needs enough in-flight work that stragglers don't drain
    # it between applies (measured: 2 gens → 1.4x, 4 → 2.7x, 6 → 3.7x
    # with staleness p99 spiking to ~24; 4 is the balanced headline)
    ab = make_api(FedBuffAPI, federated_optimizer="fedbuff",
                  async_inflight_gens=2 if quick else 4, **{
                      "async_latency_median_s": lat["latency_median_s"],
                      "async_latency_sigma": lat["latency_sigma"],
                      "async_speed_sigma": lat["speed_sigma"]})
    fb_applies = fb_to_target = None
    fb_accs = []
    stale_p50 = stale_p99 = 0.0
    t0 = time.time()
    for r in range(rounds_cap):
        m = ab.train_one_round(r)
        stale_p50, stale_p99 = m["staleness_p50"], m["staleness_p99"]
        _, acc = ab.evaluate()
        fb_accs.append(round(float(acc), 4))
        if acc >= target_acc:
            fb_applies, fb_to_target = r + 1, float(m["sim_time_s"])
            break
    fb_host_s = time.time() - t0

    # steady-state dispatch cost + the zero-recompile pin, off the
    # to-target clock.  Under the hetero partition, cohorts pad to pow2
    # step classes (the PR 2 bounded-recompile contract) and arrival
    # interleaving decides when each class / the atomic-cohort fast path
    # first fires — warm every class in the horizon explicitly so the
    # audit window measures true steady state (both programs are pure;
    # results are discarded)
    import jax as _jax
    import jax.numpy as _jnp
    from fedml_tpu.core import rng as _rng
    extra = 3 if quick else 5
    horizon = rounds_cap + extra + 4 * ab.inflight_gens
    classes: dict = {}
    for g in range(ab._next_gen, horizon):
        classes.setdefault(ab.dispatch_signature(g), g)
    for g in classes.values():
        _clients, _idx, _mask, _w, _s = ab._stage_round_arrays(g)
        _key = _rng.round_key(_rng.root_key(ab.seed), g)
        _c = ab._gather_c(np.asarray(_clients, np.int32), round_idx=g)
        _args = (ab.state, _jnp.asarray(_idx), _jnp.asarray(_mask),
                 _jnp.asarray(_w), _key, _c)
        _jax.block_until_ready(ab.round_fn(*_args)[0])
        _jax.block_until_ready(ab._dispatch_fn(*_args)[0])
    _readback(ab.state.global_params)
    with JaxRuntimeAudit() as audit:
        t0 = time.time()
        for r in range(rounds_cap, rounds_cap + extra):
            ab.train_one_round(r)
        _readback(ab.state.global_params)
        steady_s = (time.time() - t0) / extra
    out = {
        "quick": quick, "cohort": cohort, "buffer_k": ab.buffer_k,
        "total_clients": total_clients, "target_acc": target_acc,
        "latency_median_s": lat["latency_median_s"],
        "latency_sigma": lat["latency_sigma"],
        "speed_sigma": lat["speed_sigma"],
        "rounds_cap": rounds_cap,
        "sync_rounds_to_target": sync_rounds,
        "sync_sim_wallclock_to_target_s": round(sync_to_target, 2)
        if sync_to_target else None,
        "sync_final_acc": sync_accs[-1],
        "fedbuff_applies_to_target": fb_applies,
        "fedbuff_sim_wallclock_to_target_s": round(fb_to_target, 2)
        if fb_to_target else None,
        "fedbuff_final_acc": fb_accs[-1],
        # the headline: straggler-gated lockstep vs arrival-rate applies
        "async_wallclock_speedup": round(sync_to_target / fb_to_target, 3)
        if sync_to_target and fb_to_target else None,
        "fedbuff_staleness_p50_last": stale_p50,
        "fedbuff_staleness_p99_last": stale_p99,
        "fedbuff_updates_dropped": ab.updates_dropped,
        "fedbuff_clients_dispatched": ab.clients_dispatched,
        "fedbuff_fastpath_applies": ab.fastpath_applies,
        "fedbuff_steady_host_s_per_apply": round(steady_s, 5),
        "sync_host_s_total": round(sync_host_s, 2),
        "fedbuff_host_s_total": round(fb_host_s, 2),
        "steady_compiles_async": audit.compilations,
    }
    return out


# -- fedguard chaos scenario matrix (--chaos) --------------------------------
def bench_chaos(rounds: int | None = None) -> dict:
    """--chaos: the fedguard fault-tolerance matrix over the REAL
    multi-rank two-tier driver (docs/FAULT_TOLERANCE.md).  Four runs of
    ``run_silo_federation`` (1 server + 3 silos on the message plane,
    reliable delivery + heartbeat leases on):

    - **clean** — no faults; the wall-clock and final-loss baseline,
      checked for parity against the in-process ``HierarchicalSiloAPI``
      (the wire adds serialization, not math);
    - **crash_silo** — one silo dies mid-run; every remaining round
      closes at quorum 2/3 within the deadline, and the final loss stays
      within tolerance of clean (the missing silo's cohort slice is the
      only divergence);
    - **partition_heal** — a directional silo→server partition spans two
      mid rounds, then heals; the quorum trajectory dips and recovers;
    - **kill_rank0** — the coordinator is killed between rounds and
      restarted; it resumes from checkpoint + applied-round WAL with
      ZERO double-applied rounds.

    Plus the compile-stability pin: quorum closes pad the arrived set
    with zero partials, so the server combine keeps ONE compiled shape —
    JaxRuntimeAudit must count 0 steady-state compiles across varying
    quorum sizes.  FEDML_CHAOS_QUICK=1 shrinks rounds for the tier-1
    smoke.  Ranks run as threads over the hermetic local backend — the
    same comm/chaos/reliability stack as the OS-process runs in
    ``tests/test_fedguard_chaos.py``, minus the fork cost."""
    import tempfile
    import threading

    import jax

    import fedml_tpu
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu.core import federated
    from fedml_tpu.core.distributed.communication.fault_injection import (
        SiloCrashed)
    from fedml_tpu.core.distributed.communication.local import (
        local_comm_manager)
    from fedml_tpu.core.distributed.reliability import RoundWAL
    from fedml_tpu.store.hierarchy import (HierarchicalSiloAPI,
                                           run_silo_federation)

    quick = os.environ.get("FEDML_CHAOS_QUICK") == "1"
    num_silos = 3
    n_rounds = rounds or (5 if quick else 10)
    crash_round = 2 if quick else 3
    deadline_s = 1.0 if quick else 2.0
    guard_args = dict(
        reliable_delivery=True, quorum=2, quorum_deadline_s=deadline_s,
        heartbeat_interval_s=0.2, lease_s=1.5,
        retry_base_s=0.05, retry_deadline_s=5.0,
        comm_recv_timeout_s=60.0)

    def make_args(rank, run_id, **over):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=6 * 4 * BATCH, test_size=64, model="lr",
            client_num_in_total=12, client_num_per_round=6,
            comm_round=n_rounds, epochs=1, batch_size=BATCH,
            learning_rate=0.1, random_seed=7, partition_method="homo",
            num_silos=num_silos, frequency_of_the_test=10 ** 9,
            rank=rank, backend="local", run_id=run_id)
        args.update(**over)
        return fedml_tpu.init(args, should_init_logs=False)

    def run_rank(rank, run_id, out, **over):
        args = make_args(rank, run_id, **over)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        try:
            out[rank] = run_silo_federation(args, None, dataset, model)
        except SiloCrashed as e:
            out[f"crash{rank}"] = str(e)

    def federate(run_id, server_over=None, silo_over=None,
                 restart_rank0=None):
        """One full federation: silos as threads, server in this thread;
        ``restart_rank0`` re-runs the server with those overrides after
        its first life crashes."""
        out: dict = {}
        ths = [threading.Thread(
            target=run_rank, args=(r, run_id, out),
            kwargs=dict(**guard_args, **(silo_over or {})), daemon=True)
            for r in range(1, num_silos + 1)]
        for t in ths:
            t.start()
        t0 = time.time()
        run_rank(0, run_id, out, **guard_args, **(server_over or {}))
        if restart_rank0 is not None:
            assert "crash0" in out, "server did not crash as scheduled"
            run_rank(0, run_id, out, **guard_args, **restart_rank0)
        wall = time.time() - t0
        for t in ths:
            t.join(timeout=120)
        local_comm_manager.reset_run(run_id)
        return out, wall

    # -- clean baseline + in-process parity ------------------------------
    out, clean_wall = federate("chaos_clean")
    clean_hist = out[0]
    assert len(clean_hist) == n_rounds
    clean_loss = clean_hist[-1]["train_loss"]
    ref = make_args(0, "chaos_ref")
    dataset, out_dim = data_mod.load(ref)
    api = HierarchicalSiloAPI(ref, None, dataset,
                              model_mod.create(ref, out_dim))
    ref_loss = None
    for r in range(n_rounds):
        ref_loss = float(api.train_one_round(r)["train_loss"])
    wire_vs_inprocess = abs(clean_loss - ref_loss)

    # -- compile stability: ONE combine shape at every quorum size --------
    # (zero partials pad the arrived set, so 3/3, 2/3 and 1/3 closes hit
    # the same compiled program — warm once, then audit across sizes)
    parts = [api.silo_partial(n_rounds, i)[0] for i in range(num_silos)]
    host = [jax.tree_util.tree_map(np.asarray, p) for p in parts]
    api.apply_partials(host)   # warm the S-ary combine
    _readback(api.state.global_params)   # and the readback reduction
    with JaxRuntimeAudit() as audit:
        for q in (3, 2, 1, 2, 3):
            got = host[:q]
            pad = [federated.zero_like_partial(host[0])] * (num_silos - q)
            api.apply_partials(got + pad)
        _readback(api.state.global_params)
    steady_compiles = audit.compilations

    # -- scenario: crash one silo mid-run --------------------------------
    out, crash_wall = federate(
        "chaos_crash",
        silo_over=dict(chaos_crash_rank=num_silos,
                       chaos_crash_round=crash_round,
                       chaos_crash_mode="raise"))
    crash_hist = out[0]
    assert f"crash{num_silos}" in out, "silo did not crash as scheduled"
    crash_rounds_completed = len(crash_hist)
    crash_quorums = [h["quorum"] for h in crash_hist]
    crash_loss = crash_hist[-1]["train_loss"]

    # -- scenario: partition-and-heal ------------------------------------
    # A lease-dead rank is live again only once a heartbeat of its has
    # landed (every 0.2 s), and the server does not wait for a dead one: in
    # rounds of a few ms, whether the healed silo's partial makes the last
    # round is a race between three threads.  A straggler holds every round
    # open for longer than a heartbeat, as a real round is: the healed
    # silo's partial is in before the live quorum completes.
    part_spec = f"1>0:{crash_round}-{crash_round + 1}"
    out, part_wall = federate(
        "chaos_part",
        silo_over=dict(chaos_partition=part_spec, silo_slow_rank=2,
                       silo_slow_s=0.5),
        server_over=dict(chaos_partition=part_spec))
    part_hist = out[0]
    part_quorums = [h["quorum"] for h in part_hist]

    # -- scenario: kill-and-restart rank 0 -------------------------------
    ckpt_dir = tempfile.mkdtemp(prefix="fedguard_bench_wal_")
    out, kill_wall = federate(
        "chaos_kill",
        server_over=dict(checkpoint_dir=ckpt_dir,
                         chaos_crash_rank=0,
                         chaos_crash_round=crash_round,
                         chaos_crash_mode="raise"),
        restart_rank0=dict(checkpoint_dir=ckpt_dir))
    kill_hist = out[0]
    wal_rounds = RoundWAL(ckpt_dir).rounds()
    double_applied = len(wal_rounds) - len(set(wal_rounds))

    return {
        "quick": quick, "num_silos": num_silos, "rounds": n_rounds,
        "quorum": guard_args["quorum"],
        "quorum_deadline_s": deadline_s,
        "crash_round": crash_round,
        # clean + parity
        "clean_wall_s": round(clean_wall, 2),
        "clean_final_loss": round(clean_loss, 6),
        "wire_vs_inprocess_loss_delta": round(wire_vs_inprocess, 8),
        # crash-one-silo headline
        "rounds_completed_under_chaos": crash_rounds_completed,
        "crash_quorum_trajectory": crash_quorums,
        "crash_final_loss": round(crash_loss, 6),
        "crash_loss_delta_vs_clean": round(abs(crash_loss - clean_loss),
                                           6),
        "crash_wall_s": round(crash_wall, 2),
        "wallclock_overhead_vs_clean": round(crash_wall / clean_wall, 3),
        # partition-and-heal
        "partition_spec": part_spec,
        "partition_rounds_completed": len(part_hist),
        "partition_quorum_trajectory": part_quorums,
        "partition_healed": part_quorums[-1] == num_silos,
        "partition_wall_s": round(part_wall, 2),
        # kill-and-restart rank 0
        "kill_rank0_resumed_rounds": [h["round"] for h in kill_hist],
        "kill_rank0_wal_rounds": wal_rounds,
        "kill_rank0_double_applied": double_applied,
        "kill_rank0_wall_s": round(kill_wall, 2),
        # compile stability across quorum sizes
        "steady_compiles_quorum": steady_compiles,
    }


# -- fedwire quantized-wire benchmark (--wire) -------------------------------
def bench_wire(rounds: int | None = None) -> dict:
    """--wire: the fedwire localhost-DCN matrix over the REAL two-tier
    driver (docs/WIRE.md).  One federation per wire precision (1 server +
    2 silos as threads on the hermetic local backend, tracing on):

    - **off** — the legacy fp32 flax-state-dict wire, the byte and
      parity baseline;
    - **fp32 / bf16 / int8** — the fedwire codec at each precision
      (int8 with per-link error feedback);
    - **int8_overlap** — int8 plus the writer-thread compute/DCN
      overlap (silo r+1 compute overlaps the round-r upload);
    - **int8_chunk_cap** — int8, chunked frames riding reliable
      delivery, under a fedguard bandwidth cap: the graceful-degradation
      variant — rounds COMPLETE instead of stalling.

    Each run reports measured ``comm.bytes.silo_server``, the codec's
    modeled census and their ``wire_bytes_ratio`` (fedtrace summarize),
    wall clock, and final-loss delta vs the off baseline (PR 5 parity
    tolerances).  Headline: measured fp32-wire bytes over int8-wire
    bytes — the ~4x the in-mesh blockscale layer already gets, now on
    the distributed tier.  Plus the compile pin: wire decode feeds the
    SAME jitted silo/combine programs, so JaxRuntimeAudit must count 0
    steady-state compiles with the codec on.  FEDML_WIRE_QUICK=1
    shrinks rounds for the tier-1 smoke."""
    import threading

    import fedml_tpu
    from fedml_tpu import data as data_mod, model as model_mod, obs
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu.core.distributed.communication.local import (
        local_comm_manager)
    from fedml_tpu.store.hierarchy import (HierarchicalSiloAPI,
                                           run_silo_federation)

    quick = os.environ.get("FEDML_WIRE_QUICK") == "1"
    num_silos = 2
    n_rounds = rounds or (3 if quick else 8)

    def make_args(rank, run_id, **over):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=6 * 4 * BATCH, test_size=64, model="lr",
            client_num_in_total=12, client_num_per_round=6,
            comm_round=n_rounds, epochs=1, batch_size=BATCH,
            learning_rate=0.1, random_seed=7, partition_method="homo",
            num_silos=num_silos, frequency_of_the_test=10 ** 9,
            rank=rank, backend="local", run_id=run_id,
            comm_recv_timeout_s=120.0)
        args.update(**over)
        return fedml_tpu.init(args, should_init_logs=False)

    def run_rank(rank, run_id, out, **over):
        args = make_args(rank, run_id, **over)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        out[rank] = run_silo_federation(args, None, dataset, model)

    fedtrace = _import_fedtrace()

    def federate(run_id, **over):
        """One traced federation; returns (history, wall_s, summary)."""
        obs.configure(enabled=True, reset=True)
        out: dict = {}
        ths = [threading.Thread(target=run_rank, args=(r, run_id, out),
                                kwargs=over, daemon=True)
               for r in range(1, num_silos + 1)]
        for t in ths:
            t.start()
        t0 = time.time()
        run_rank(0, run_id, out, **over)
        wall = time.time() - t0
        for t in ths:
            t.join(timeout=120)
        local_comm_manager.reset_run(run_id)
        summary = fedtrace.summarize(obs.get_tracer().export_chrome())
        obs.configure(enabled=False)
        hist = out[0]
        assert len(hist) == n_rounds, \
            f"{run_id}: {len(hist)}/{n_rounds} rounds"
        return hist, wall, summary

    variants = {
        "off": {},
        "fp32": dict(wire_precision="fp32"),
        "bf16": dict(wire_precision="bf16"),
        "int8": dict(wire_precision="int8"),
        "int8_overlap": dict(wire_precision="int8", wire_overlap=True),
        # graceful degradation under fedguard's bandwidth cap: bounded
        # frames ride reliable delivery per-chunk, so the capped link
        # streams instead of stalling on one monolithic partial
        "int8_chunk_cap": dict(
            wire_precision="int8", wire_chunk_bytes=4096,
            reliable_delivery=True, retry_base_s=0.05,
            retry_deadline_s=30.0,
            chaos_bandwidth_bps=2_000_000, chaos_seed=11),
    }
    rows: dict = {}
    try:
        for name, over in variants.items():
            hist, wall, summary = federate(f"wire_{name}", **over)
            counters = summary["counters"]
            rows[name] = {
                "wall_s": round(wall, 2),
                "final_loss": round(hist[-1]["train_loss"], 6),
                "silo_server_bytes": int(
                    counters.get("comm.bytes.silo_server", 0)),
                "wire_modeled_bytes": int(
                    counters.get("wire.modeled_bytes", 0)),
            }
            if "wire_bytes_ratio" in summary:
                rows[name]["wire_bytes_ratio"] = summary[
                    "wire_bytes_ratio"]
            if "comm_chunks_sent" in summary:
                rows[name]["chunks_sent"] = int(
                    summary["comm_chunks_sent"])
    finally:
        obs.configure(enabled=False)

    base_loss = rows["off"]["final_loss"]
    for name in rows:
        rows[name]["loss_delta_vs_off"] = round(
            abs(rows[name]["final_loss"] - base_loss), 6)

    # compile pin: the codec decodes to host numpy trees with the same
    # structure every round, so the warm silo/combine programs never
    # re-trace — audit two steady-state rounds with wire int8 on
    ref = make_args(0, "wire_ref", wire_precision="int8")
    dataset, out_dim = data_mod.load(ref)
    api = HierarchicalSiloAPI(ref, None, dataset,
                              model_mod.create(ref, out_dim))
    for r in range(2):
        api.train_one_round(r)
    _readback(api.state.global_params)
    with JaxRuntimeAudit() as audit:
        for r in range(2, 4):
            api.train_one_round(r)
        _readback(api.state.global_params)
    steady_compiles = audit.compilations

    fp32_b = rows["fp32"]["silo_server_bytes"]
    int8_b = rows["int8"]["silo_server_bytes"]
    out = {
        "quick": quick, "num_silos": num_silos, "rounds": n_rounds,
        "variants": rows,
        # headline: measured wire-byte reduction, int8 vs fp32 wire
        "wire_bytes_fp32_over_int8": round(fp32_b / int8_b, 3)
        if int8_b else None,
        "wire_bytes_off_over_int8": round(
            rows["off"]["silo_server_bytes"] / int8_b, 3)
        if int8_b else None,
        "int8_loss_delta_vs_off": rows["int8"]["loss_delta_vs_off"],
        "bf16_loss_delta_vs_off": rows["bf16"]["loss_delta_vs_off"],
        "overlap_wall_s": rows["int8_overlap"]["wall_s"],
        "capped_rounds_completed": n_rounds,
        "steady_compiles_wire": steady_compiles,
    }
    # perf-regression gate (tools/fedtrace.py regress): score THIS row
    # against the committed BENCH trajectory + tolerance bands
    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        r = fedtrace.regress(
            out, fedtrace.load_bands(
                os.path.join(repo, fedtrace.DEFAULT_BANDS_FILE)),
            fedtrace.load_trajectory(repo))
        out["regress"] = {"ok": r["ok"], "checked": r["checked"],
                          "regressions": r["regressions"]}
    except (OSError, ValueError, KeyError) as e:
        out["regress"] = {"error": str(e)}
    return out


# -- fedtrace overhead + breakdown benchmark (--trace) -----------------------
def _import_fedtrace():
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import fedtrace
    return fedtrace


def bench_trace(rounds: int | None = None,
                clients_per_round: int | None = None) -> dict:
    """--trace: cost and content of the fedtrace plane on the 256-client
    MNIST-LR config.  Times steady-state rounds untraced vs. traced (the
    acceptance bar is <5% overhead — tracing adds host span bookkeeping
    only, never a device sync or compile), then drives one traced
    ``train()`` so the capture carries round/staging spans plus the
    per-round ObsCarry counters, and folds ``tools/fedtrace.py
    summarize``'s per-phase breakdown into the bench JSON.
    FEDML_TRACE_QUICK=1 shrinks the cohort for smoke tests;
    FEDML_TRACE_OUT=path additionally writes the Chrome trace file."""
    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod, obs
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    quick = os.environ.get("FEDML_TRACE_QUICK") == "1"
    cpr = clients_per_round or (16 if quick else CLIENTS_PER_ROUND)
    total = max(4 * cpr, 64) if quick else TOTAL_CLIENTS
    timed_rounds = rounds or (3 if quick else ROUNDS_TIMED)
    out = {"clients_per_round": cpr, "quick": quick}
    rtt = None

    def make_api():
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total * BATCH * STEPS_PER_CLIENT, test_size=256,
            model="lr", client_num_in_total=total, client_num_per_round=cpr,
            comm_round=10 ** 6, epochs=1, batch_size=BATCH,
            learning_rate=0.03, partition_method="homo",
            frequency_of_the_test=10 ** 9, random_seed=0,
        )
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        return FedAvgAPI(args, None, dataset, model, client_mode="vmap")

    try:
        # ONE api, interleaved untraced/traced timings, min of each pair:
        # on a loaded 1-core host, two separately-built apis measured
        # minutes apart read ~15-20% apart from load drift alone — the
        # overhead question is about the tracer, so toggle ONLY the tracer
        api = make_api()
        api.train_one_round(0)  # compile
        api.train_one_round(1)
        _readback(api.state.global_params)
        rtt = measure_rtt()
        rounds_done = [2]

        def run_n(n):
            for _ in range(n):
                api.train_one_round(rounds_done[0])
                rounds_done[0] += 1

        samples = {False: [], True: []}
        for traced in (False, True, False, True):
            obs.configure(enabled=traced, reset=traced)
            samples[traced].append(_timed_chain(
                run_n, lambda: _readback(api.state.global_params),
                min_total_s=0.5 if quick else 2.0, n0=timed_rounds,
                rtt=rtt))
        out["untraced_s_per_round"] = round(min(samples[False]), 5)
        out["traced_s_per_round"] = round(min(samples[True]), 5)
        out["timing_samples"] = {
            "untraced": [round(s, 5) for s in samples[False]],
            "traced": [round(s, 5) for s in samples[True]]}

        # a short traced train() run so the capture flushes the per-round
        # ObsCarry counters (the timed loop above defers them); rounds are
        # pure functions of the index, so re-running 0..N on the warm
        # program is cheap and deterministic
        obs.configure(enabled=True, reset=True)
        api.comm_rounds = 4 if quick else 8
        api.eval_freq = 2
        api.train()
        # fedscope measured device time: run the out-of-band phase probe
        # so the BENCH row archives how far the FLOP-proxy attribution
        # sits from measured reality (FEDML_TRACE_DEVICE=0 opts out)
        if os.environ.get("FEDML_TRACE_DEVICE") != "0":
            from fedml_tpu.obs.devicetime import measure_device_phases
            measure_device_phases(api)
        trace = obs.get_tracer().export_chrome()
        fedtrace = _import_fedtrace()
        summary = fedtrace.summarize(trace)
        out["phases"] = summary["phases"]
        out["trace_rounds"] = summary["rounds"]
        out["trace_events"] = len(trace["traceEvents"])
        for k in ("device_phase_source", "device_phases_measured_s",
                  "device_phase_delta"):
            if k in summary:
                out[k] = summary[k]
        # perf-regression gate (tools/fedtrace.py regress): score THIS
        # row against the committed BENCH trajectory + tolerance bands
        repo = os.path.dirname(os.path.abspath(__file__))
        try:
            r = fedtrace.regress(
                out, fedtrace.load_bands(
                    os.path.join(repo, fedtrace.DEFAULT_BANDS_FILE)),
                fedtrace.load_trajectory(repo))
            out["regress"] = {"ok": r["ok"], "checked": r["checked"],
                              "regressions": r["regressions"]}
        except (OSError, ValueError, KeyError) as e:
            out["regress"] = {"error": str(e)}
        tp = os.environ.get("FEDML_TRACE_OUT")
        if tp:
            obs.get_tracer().export_chrome(tp)
            out["trace_path"] = tp
    finally:
        obs.configure(enabled=False)
    out["trace_overhead_pct"] = round(
        100.0 * (out["traced_s_per_round"] / out["untraced_s_per_round"]
                 - 1.0), 2)
    return out


# -- fedmon federation-health benchmark (--health) ---------------------------
def bench_health(rounds: int | None = None) -> dict:
    """--health: the fedmon federation-health plane (ISSUE 14,
    docs/OBSERVABILITY.md) on a LABEL-FLIP injection scenario.

    Trains sp FedAvg with 10% of clients' labels flipped and ``health``
    on, with the live ``/metrics`` + ``/healthz`` endpoint up for the
    whole run: scrapes BOTH mid-run (prometheus parse of the health
    gauges) and around a deliberately violated straggler SLO
    (round-time bound of 1µs ⇒ ``/healthz`` must transition
    ok→degraded), then scores the detector against the known flipped
    set (acceptance: precision ≥ 0.9 AND recall ≥ 0.9) and times
    steady-state rounds health-off vs health-on interleaved (acceptance:
    ≤ 3% overhead — the per-client stat rows are a few reductions inside
    the already-compiled round).  FEDML_HEALTH_QUICK=1 shrinks the run
    for the tier-1 smoke (3 timed rounds, 64 clients)."""
    import json as json_mod
    import tempfile
    import threading
    import urllib.request

    import fedml_tpu
    from fedml_tpu.arguments import load_arguments
    from fedml_tpu import data as data_mod, model as model_mod, obs
    from fedml_tpu.obs.metricsd import parse_prometheus_text, prom_value
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    quick = os.environ.get("FEDML_HEALTH_QUICK") == "1"
    total = 64 if quick else CLIENTS_PER_ROUND
    cpr = 32 if quick else CLIENTS_PER_ROUND // 2
    det_rounds = 6 if quick else 12
    timed_rounds = rounds or (3 if quick else ROUNDS_TIMED)
    n_flip = max(1, total // 10)
    out = {"quick": quick, "clients": total, "clients_per_round": cpr,
           "flipped_clients": n_flip, "detection_rounds": det_rounds}

    def make_api(health, flip, **over):
        args = load_arguments()
        args.update(
            dataset="synthetic", num_classes=NUM_CLASSES, input_shape=IMG,
            train_size=total * BATCH * STEPS_PER_CLIENT, test_size=256,
            model="lr", client_num_in_total=total,
            client_num_per_round=cpr, comm_round=10 ** 6, epochs=1,
            batch_size=BATCH, learning_rate=0.03, partition_method="homo",
            frequency_of_the_test=10 ** 9, random_seed=0, health=health,
        )
        args.update(**over)
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        flipped = []
        if flip:
            rng = np.random.default_rng(0)
            flipped = sorted(rng.choice(total, size=n_flip,
                                        replace=False).tolist())
            for c in flipped:
                idx = dataset.client_idxs[c]
                dataset.train_y[idx] = (NUM_CLASSES - 1) \
                    - dataset.train_y[idx]
        model = model_mod.create(args, out_dim)
        return FedAvgAPI(args, None, dataset, model,
                         client_mode="vmap"), flipped

    # -- overhead: health-off vs health-on, interleaved min-of-pairs -------
    api_off, _ = make_api(health=False, flip=False)
    api_on, _ = make_api(health=True, flip=False)
    for api in (api_off, api_on):
        api.train_one_round(0)   # compile
        api.train_one_round(1)
        _readback(api.state.global_params)
    rtt = measure_rtt()
    done = {id(api_off): [2], id(api_on): [2]}

    def run_n_for(api):
        def run_n(n):
            for _ in range(n):
                api.train_one_round(done[id(api)][0])
                done[id(api)][0] += 1
        return run_n

    samples = {False: [], True: []}
    for on in (False, True, False, True):
        api = api_on if on else api_off
        samples[on].append(_timed_chain(
            run_n_for(api), lambda a=api: _readback(a.state.global_params),
            min_total_s=0.5 if quick else 2.0, n0=timed_rounds, rtt=rtt))
    out["plain_s_per_round"] = round(min(samples[False]), 5)
    out["health_s_per_round"] = round(min(samples[True]), 5)
    out["health_overhead_pct"] = round(
        100.0 * (out["health_s_per_round"] / out["plain_s_per_round"]
                 - 1.0), 2)

    # -- detection scenario with the live endpoint up ----------------------
    # deliberately-violated straggler SLO: any real round breaches 1µs,
    # so /healthz must transition ok -> degraded once rounds flow
    slo = tempfile.NamedTemporaryFile("w", suffix=".yaml", delete=False)
    slo.write("slos:\n"
              "  - name: straggler_round_time\n"
              "    metric: health.round_time_s\n"
              "    max: 0.000001\n"
              "  - name: anomaly_rate\n"
              "    metric: health.anomaly_rate\n"
              "    max: 0.5\n")
    slo.close()
    obs.configure(enabled=True, reset=True)
    try:
        # frequency_of_the_test=1: fedmon observes at the driver's flush,
        # so a LIVE health run flushes every round (the overhead numbers
        # above measure the deferred-flush steady state separately)
        api, flipped = make_api(health=True, flip=True, metrics_port=0,
                                health_slo_path=slo.name, trace=True,
                                frequency_of_the_test=1)
        api.comm_rounds = det_rounds
        url = api.metrics_server.url
        with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
            out["healthz_before"] = json_mod.loads(r.read())["status"]

        mid: dict = {}

        def scrape_mid():
            # poll until the first flushed round's gauges appear (round 0
            # includes the compile), then record the LIVE snapshot
            deadline = time.time() + 60.0
            try:
                while time.time() < deadline:
                    with urllib.request.urlopen(url + "/metrics",
                                                timeout=10) as r:
                        samples_ = parse_prometheus_text(r.read().decode())
                    ro = prom_value(samples_, "fedmon_gauge",
                                    name="health.rounds_observed")
                    if ro:
                        mid["rounds_observed"] = ro
                        mid["anomaly_rate"] = prom_value(
                            samples_, "fedmon_gauge",
                            name="health.anomaly_rate")
                        return
                    time.sleep(0.05)
                mid["error"] = "no fedmon gauges before deadline"
            except Exception as e:
                mid["error"] = repr(e)

        scraper = threading.Thread(target=scrape_mid, daemon=True)
        scraper.start()
        api.train()
        scraper.join(timeout=90.0)
        with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
            hz = json_mod.loads(r.read())
        out["healthz_after"] = hz["status"]
        out["healthz_transition_ok"] = (out["healthz_before"] == "ok"
                                        and hz["status"] == "degraded")
        out["mid_run_scrape"] = mid
        flagged = api.health_monitor.flagged()
        tp = len(set(flagged) & set(flipped))
        fp = len(set(flagged) - set(flipped))
        out["detector_precision"] = round(tp / max(tp + fp, 1), 4)
        out["detector_recall"] = round(tp / max(len(flipped), 1), 4)
        out["flagged_count"] = len(flagged)
        out["health_gauges"] = {k: round(v, 6) for k, v in
                                api.health_monitor.gauges().items()}
        # offline report parity: the captured trace replays to the same
        # flagged set through tools/fedtrace.py health
        fedtrace = _import_fedtrace()
        h = fedtrace.health_report(obs.get_tracer().export_chrome())
        out["offline_report_flagged_matches"] = \
            h["flagged_clients"] == flagged
        api.metrics_server.close()
    finally:
        obs.configure(enabled=False)
        os.unlink(slo.name)

    # perf-regression gate (tools/fedtrace.py regress) over this row
    fedtrace = _import_fedtrace()
    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        r = fedtrace.regress(
            out, fedtrace.load_bands(
                os.path.join(repo, fedtrace.DEFAULT_BANDS_FILE)),
            fedtrace.load_trajectory(repo))
        out["regress"] = {"ok": r["ok"], "checked": r["checked"],
                          "regressions": r["regressions"]}
    except (OSError, ValueError, KeyError) as e:
        out["regress"] = {"error": str(e)}
    return out


# -- LLM LoRA single-chip benchmark ------------------------------------------
def bench_llm_lora(on_accelerator: bool, peak: float | None,
                   batch: int | None = None, remat: str | None = None,
                   flash_mode: str | None = None) -> dict:
    """Single-chip LoRA fine-tune step on a Llama (bf16 on TPU): step time,
    tokens/sec, MFU with LoRA-aware FLOPs ((4*N + 6*r)*T — frozen base
    weights pay forward + activation-grad matmuls but no weight-grad
    matmuls), and the flash-vs-blockwise forward ratio on the same shapes.

    ``batch``/``remat``/``flash_mode`` override the default config for the
    --llm-ablate grid (docs/MFU_ROOFLINE.md levers); flash_mode sets
    FEDML_TPU_FLASH_MODE for the fresh traces this call makes and restores
    the prior value on exit (the gate is read per-trace)."""
    prev = os.environ.get("FEDML_TPU_FLASH_MODE")
    if flash_mode is not None:
        os.environ["FEDML_TPU_FLASH_MODE"] = flash_mode
    try:
        return _bench_llm_lora_impl(on_accelerator, peak, batch, remat,
                                    flash_mode)
    finally:
        if flash_mode is not None:
            if prev is None:
                os.environ.pop("FEDML_TPU_FLASH_MODE", None)
            else:
                os.environ["FEDML_TPU_FLASH_MODE"] = prev


def _bench_llm_lora_impl(on_accelerator, peak, batch, remat,
                         flash_mode) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM, causal_nll

    if on_accelerator:
        # remat="dots": activations fit comfortably at this scale, so pay
        # HBM for ~25-30% fewer recompute FLOPs in backward
        cfg = LlamaConfig(vocab_size=16384, dim=1024, n_layers=12, n_heads=16,
                          n_kv_heads=8, ffn_dim=2816, max_seq_len=1024,
                          dtype=jnp.bfloat16, lora_rank=8,
                          remat=remat or "dots")
        batch, seq, steps = batch or 4, 1024, 10
    else:  # CPU fallback: small shapes for wall-clock sanity, but the
        # SHIPPED dtype (bf16) so the bench measures the real configuration
        cfg = LlamaConfig(vocab_size=2048, dim=256, n_layers=4, n_heads=8,
                          n_kv_heads=4, ffn_dim=512, max_seq_len=256,
                          dtype=jnp.bfloat16, lora_rank=8,
                          remat=remat or "full")
        batch, seq, steps = batch or 2, 256, 3

    model = LlamaLM(cfg)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (batch, seq), 0, cfg.vocab_size)
    variables = model.init(rng, tokens)
    params, lora = variables["params"], variables.get("lora", {})
    # randomize A so adapters actually train
    lora = jax.tree.map(
        lambda x: jax.random.normal(rng, x.shape, x.dtype) * 0.02
        if x.shape[-1] == cfg.lora_rank else x, lora)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    n_lora = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(lora))

    opt = optax.sgd(1e-3)
    opt_state = opt.init(lora)

    def loss_fn(lora, params, tokens):
        logits = model.apply({"params": params, "lora": lora}, tokens,
                             train=True)
        return causal_nll(logits[:, :-1], tokens[:, 1:])

    @jax.jit
    def step(lora, opt_state, params, tokens):
        loss, g = jax.value_and_grad(loss_fn)(lora, params, tokens)
        upd, opt_state = opt.update(g, opt_state)
        return optax.apply_updates(lora, upd), opt_state, loss

    state = [step(lora, opt_state, params, tokens)]  # compile
    _readback(state[0][2])
    rtt = measure_rtt()

    def run_n(n):
        lora2, opt_state2, _ = state[0]
        for _ in range(n):
            lora2, opt_state2, loss = step(lora2, opt_state2, params, tokens)
        state[0] = (lora2, opt_state2, loss)

    dt = _timed_chain(run_n, lambda: _readback(state[0][2]), n0=steps,
                      rtt=rtt)

    tokens_per_step = batch * seq
    # LoRA training FLOPs: frozen base weights pay forward (2NT) and
    # activation-gradient (2NT) matmuls but NOT weight-grad matmuls; the
    # adapters pay the full 6T per param.  (6NT would overstate MFU ~1.5x.)
    flops = (4.0 * n_params + 6.0 * n_lora) * tokens_per_step
    final_loss = float(np.asarray(state[0][2]))
    out = {
        "step_time_s": round(dt, 5),
        "tokens_per_sec": round(tokens_per_step / dt, 1),
        "n_params": n_params,
        "n_lora_params": n_lora,
        # a non-finite loss would be a regression of the round-3 bf16
        # accumulation fix (ops/attention.py preferred_element_type)
        "loss_finite": bool(np.isfinite(final_loss)),
        "mfu": round(flops / dt / peak, 4) if peak else None,
        "config": {"dim": cfg.dim, "layers": cfg.n_layers, "seq": seq,
                   "batch": batch, "lora_rank": cfg.lora_rank,
                   "remat": cfg.remat,
                   "dtype": str(cfg.dtype.__name__ if hasattr(cfg.dtype, "__name__") else cfg.dtype)},
    }

    # flash vs blockwise forward ratio on attention shapes from this model
    if on_accelerator and flash_mode is None:
        out["flash_vs_blockwise_speedup"] = _attn_speedup(
            b=batch, h=cfg.n_heads, s=seq, d=cfg.dim // cfg.n_heads,
            dtype=jnp.bfloat16)
    return out


def _attn_speedup(b, h, s, d, dtype, causal: bool = True,
                  reps: int = 20) -> float:
    """Forward-only flash vs blockwise timing.  Each timing chains ``reps``
    attention calls (output feeds the next query — attention outputs are
    convex combinations of v, so magnitudes stay bounded) inside one jit so
    a single final readback forces the whole chain."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.attention import (blockwise_attention,
                                         flash_attention_fwd_pallas)

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype)

    def chained(fn):
        def many(q, k, v):
            def body(c, _):
                return fn(c, k, v), ()
            out, _ = jax.lax.scan(body, q, None, length=reps)
            return jnp.sum(out.astype(jnp.float32))
        return jax.jit(many)

    fl = chained(
        lambda q, k, v: flash_attention_fwd_pallas(q, k, v, causal))
    bw = chained(lambda q, k, v: blockwise_attention(q, k, v, causal=causal))
    rtt = measure_rtt()
    t_fl, t_bw = (_per_call_time(f, (q, k, v), reps, rtt)
                  for f in (fl, bw))
    return round(t_bw / t_fl, 2)


def _per_call_time(f, args, reps, rtt):
    """Per-inner-call time of jitted ``f`` (whose body chains ``reps``
    applications of the op): dispatch f back-to-back n times — async
    dispatches pipeline in device program order, so the single final
    readback forces them all — with _timed_chain growing n until
    wall-clock >= 2s.  This AMORTIZES the read-back instead of
    subtracting it from a single short run."""
    _readback(f(*args))  # compile
    state = {}

    def run_n(n):
        for _ in range(n):
            state["o"] = f(*args)

    dt = _timed_chain(run_n, lambda: _readback(state["o"]), n0=2, rtt=rtt)
    return dt / reps


def _attn_step_speedup(b, h, s, d, dtype, causal: bool = True,
                       reps: int = 10) -> float:
    """Fwd+bwd (training-step) flash vs blockwise timing: grad of a chained
    scan of attention calls, one readback forcing the whole chain (VERDICT
    r3 item 3: the committed sweep must time the backward too).  The flash
    side compiles under FEDML_TPU_FLASH_MODE=force so the measurement
    bypasses the autotune-or-fallback gate it feeds."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops import attention as A

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype)

    def make(fn):
        def many(q, k, v):
            def body(c, _):
                return fn(c, k, v), ()
            out, _ = jax.lax.scan(body, q, None, length=reps)
            return jnp.sum(out.astype(jnp.float32))
        return jax.jit(jax.grad(many))

    rtt = measure_rtt()
    old = os.environ.get("FEDML_TPU_FLASH_MODE")
    os.environ["FEDML_TPU_FLASH_MODE"] = "force"
    try:
        fl = make(lambda q, k, v: A.flash_attention(q, k, v, causal))
        _readback(fl(q, k, v))  # compile (traces under force mode)
    finally:
        if old is None:
            os.environ.pop("FEDML_TPU_FLASH_MODE", None)
        else:
            os.environ["FEDML_TPU_FLASH_MODE"] = old
    bw = make(lambda q, k, v: A.blockwise_attention(q, k, v, causal=causal))
    _readback(bw(q, k, v))
    t_fl, t_bw = (_per_call_time(f, (q, k, v), reps, rtt)
                  for f in (fl, bw))
    return round(t_bw / t_fl, 2)


def _gqa_grouped_speedup(b, h, kvh, s, d, dtype, causal, reps: int = 10):
    """Index-mapped grouped KV vs materialized jnp.repeat, forward only."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.attention import flash_attention_fwd_pallas

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kvh, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kvh, s, d), dtype)

    def chained(fn):
        def many(q, k, v):
            def body(c, _):
                return fn(c, k, v), ()
            out, _ = jax.lax.scan(body, q, None, length=reps)
            return jnp.sum(out.astype(jnp.float32))
        return jax.jit(many)

    grouped = chained(
        lambda q, k, v: flash_attention_fwd_pallas(q, k, v, causal))
    rep = h // kvh
    repeated = chained(
        lambda q, k, v: flash_attention_fwd_pallas(
            q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1), causal))
    rtt = measure_rtt()
    t_grouped, t_repeated = (_per_call_time(f, (q, k, v), reps, rtt)
                             for f in (grouped, repeated))
    return round(t_repeated / t_grouped, 2)


# -- attention parity + timing sweep (--attn) --------------------------------
def attn_sweep() -> dict:
    """Flash(Pallas) vs blockwise: numerics + timing across S, causal, dtype,
    GQA.  On non-TPU backends the Pallas side is skipped (reported null)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.ops.attention import (blockwise_attention,
                                         flash_attention_fwd_pallas)

    on_tpu = jax.default_backend() == "tpu"
    cases = []
    # f32 tolerance is platform-dependent: TPU MXU computes f32 dots via
    # bf16 passes by default (jax default matmul precision), so two
    # differently-blocked softmax-attention implementations legitimately
    # diverge by ~1e-3 in f32 on TPU while agreeing to 2e-5 on CPU.
    f32_tol = 2e-3 if on_tpu else 2e-5
    for s in (512, 2048, 4096):
        for causal in (True, False):
            for dtype, tol in ((jnp.float32, f32_tol), (jnp.bfloat16, 2e-2)):
                for h, kvh in ((8, 8), (8, 2)):  # MHA and GQA-repeated layout
                    b, d = 1, 128
                    ks = jax.random.split(jax.random.PRNGKey(s + h), 3)
                    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
                    k = jax.random.normal(ks[1], (b, kvh, s, d), dtype)
                    v = jax.random.normal(ks[2], (b, kvh, s, d), dtype)
                    case = {"S": s, "causal": causal,
                            "dtype": dtype.__name__, "heads": f"{h}q/{kvh}kv"}
                    if on_tpu:
                        # grouped KV consumed natively (no repeat)
                        ref = blockwise_attention(q, k, v, causal=causal)
                        out = flash_attention_fwd_pallas(q, k, v, causal)
                        err = float(jnp.max(jnp.abs(
                            out.astype(jnp.float32) - ref.astype(jnp.float32))))
                        case["max_abs_err"] = err
                        case["pass"] = bool(err < tol)
                        if kvh == h:
                            case["speedup"] = _attn_speedup(
                                b, h, s, d, dtype, causal=causal, reps=10)
                            if causal:
                                case["step_speedup_fwd_bwd"] = \
                                    _attn_step_speedup(b, h, s, d, dtype,
                                                       causal=causal)
                        else:
                            case["gqa_grouped_vs_repeat"] = \
                                _gqa_grouped_speedup(b, h, kvh, s, d, dtype,
                                                     causal)
                    else:
                        case["max_abs_err"] = None
                        case["pass"] = None
                    cases.append(case)
    n_checked = sum(1 for c in cases if c["pass"] is not None)
    n_pass = sum(1 for c in cases if c["pass"])
    return {
        "metric": "flash_attention_parity",
        "value": n_pass,
        "unit": f"cases_passed_of_{n_checked}",
        "vs_baseline": None,
        "on_tpu": on_tpu,
        "cases": cases,
    }


# -- serving-plane benchmark (--serve) ---------------------------------------
def serve_bench(on_accelerator: bool) -> dict:
    """tokens/sec for the serving decode paths on one chip: plain
    full-buffer, KV-cached, continuous batching (4 slots), and int8
    weight-only quantized variants of the cached paths."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.llm.quantization import quantize_params_int8
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    from fedml_tpu.serving.templates.openai_compat import generate

    if on_accelerator:
        cfg = LlamaConfig(vocab_size=8192, dim=512, n_layers=8, n_heads=8,
                          n_kv_heads=4, ffn_dim=1408, max_seq_len=512,
                          dtype=jnp.bfloat16, lora_rank=0)
        buf, n_new, slots = 512, 64, 4
    else:
        cfg = LlamaConfig(vocab_size=258, dim=64, n_layers=2, n_heads=4,
                          n_kv_heads=4, ffn_dim=128, max_seq_len=256,
                          dtype=jnp.float32, lora_rank=0)
        buf, n_new, slots = 256, 48, 4
    model = LlamaLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    qtree, qstats = quantize_params_int8(params)
    apply_fn = lambda p, t: model.apply({"params": p}, t)
    prompt = [5, 17, 42]

    def timed_generate(p, use_model, reps=1):
        generate(apply_fn, p, prompt, max_new_tokens=4, buf_len=buf,
                 model=model if use_model else None)  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = generate(apply_fn, p, prompt, max_new_tokens=n_new,
                           buf_len=buf, model=model if use_model else None)
        dt = (time.perf_counter() - t0) / reps
        return round(len(out) / dt, 1)

    # FEDML_SERVE_QUICK=1 trims the int8-weight engine variants (each one
    # pays its own compile).  Progress lines go to stdout after every row
    # so a timeout still leaves evidence.
    quick = os.environ.get("FEDML_SERVE_QUICK") == "1"

    def _row(name, value, out):
        out[name] = value
        print(f"[serve-row] {name}={value} t={time.perf_counter():.0f}",
              flush=True)

    result = {"serve_quick": quick}  # provenance: trimmed battery or full
    _row("plain_tok_s", timed_generate(params, False), result)
    _row("kv_cached_tok_s", timed_generate(params, True, reps=3), result)
    if not quick:
        _row("kv_cached_int8_tok_s", timed_generate(qtree, True, reps=3),
             result)
    result["int8_weight_bytes_ratio"] = round(qstats["ratio"], 3)

    # prefix caching: N requests sharing one long system prompt — the
    # cached runs skip the shared prefill (round-4 lever; federated-eval
    # templates make this the common serving shape)
    from fedml_tpu.serving.templates.openai_compat import PrefixCache
    sys_prompt = list(range(2, 2 + (128 if on_accelerator else 64)))
    reqs = [sys_prompt + [200 + i] for i in range(4)]

    def _timed_prefix_run(request_list, pc):
        t0 = time.perf_counter()
        total = 0
        for r in request_list:
            total += len(generate(apply_fn, params, r,
                                  max_new_tokens=8, buf_len=buf,
                                  model=model, prefix_cache=pc))
        return round(total / (time.perf_counter() - t0), 1)

    def shared_prefix_run(pc):
        return _timed_prefix_run(reqs, pc)

    generate(apply_fn, params, reqs[0], max_new_tokens=2, buf_len=buf,
             model=model)                                     # compile
    _row("shared_prefix_tok_s", shared_prefix_run(None), result)
    pc = PrefixCache(capacity=8)
    _row("shared_prefix_cached_tok_s", shared_prefix_run(pc), result)
    result["prefix_cache_hits"] = pc.stats["hits"]
    result["prefix_tokens_skipped"] = pc.stats["prefill_tokens_skipped"]

    # partial hits with a MULTI-token uncached tail (round-5 tail_block
    # lever: the tail replays as ONE dispatch, so this row isolates the
    # dispatch-amortization a per-token replay would forfeit — the
    # decisive case over a network-attached chip)
    tail_reqs = [sys_prompt + [210 + i + j for j in range(12)]
                 for i in range(4)]

    def tail_run(pc2):
        return _timed_prefix_run(tail_reqs, pc2)

    # compile BOTH replay paths outside the timed window: a miss-path
    # prefill AND a partial-hit tail_block (the warm cache below forces
    # the block program to trace now, not inside the cached timing)
    warm_pc = PrefixCache(capacity=2)
    generate(apply_fn, params, sys_prompt, max_new_tokens=1, buf_len=buf,
             model=model, prefix_cache=warm_pc)
    generate(apply_fn, params, tail_reqs[0], max_new_tokens=2, buf_len=buf,
             model=model, prefix_cache=warm_pc)
    _row("prefix_tail12_tok_s", tail_run(None), result)
    pc_t = PrefixCache(capacity=8)
    generate(apply_fn, params, sys_prompt, max_new_tokens=1, buf_len=buf,
             model=model, prefix_cache=pc_t)                  # warm prefix
    _row("prefix_tail12_cached_tok_s", tail_run(pc_t), result)
    result["prefix_tail12_hits"] = pc_t.stats["hits"]

    # horizon>1 amortizes per-token host dispatch (dominant over a
    # network-attached TPU) by scanning H decode steps on-device per tick;
    # the kv-int8 row additionally stores the KV cache int8 (halved HBM
    # reads on the decode-dominant stream)
    horizon = 16 if on_accelerator else 8
    kv8_model = LlamaLM(dataclasses.replace(cfg, kv_cache_dtype="int8"))
    variants = [
        ("batched_tok_s", model, params, 1),
        ("batched_int8_tok_s", model, qtree, 1),
        (f"batched_h{horizon}_tok_s", model, params, horizon),
        (f"batched_h{horizon}_int8_tok_s", model, qtree, horizon),
        (f"batched_h{horizon}_kvint8_tok_s", kv8_model, params, horizon)]
    if quick:  # keep the dense baseline + best-horizon + the KV-bytes lever
        variants = [v for v in variants if "_int8" not in v[0]
                    or "kvint8" in v[0]]
    for name, m, p, h in variants:
        engine = ContinuousBatchingEngine(m, p, slots=slots, buf_len=buf,
                                          horizon=h)
        try:
            engine.generate(prompt, max_new_tokens=2)  # compile
            t0 = time.perf_counter()
            qs = [engine.submit([i + 1, i + 2, i + 3], max_new_tokens=n_new)
                  for i in range(slots)]
            total = 0
            for q in qs:
                while q.get() is not None:
                    total += 1
            _row(name, round(total / (time.perf_counter() - t0), 1), result)
        finally:
            engine.stop()
    return result


# -- multi-tenant serving benchmark (--serve-mt) -----------------------------
def serve_mt_bench() -> dict:
    """ONE engine serving N registered LoRA adapters against one shared
    base (ISSUE 9): aggregate tokens/s vs an adapter-blind engine at the
    same slot count, a JaxRuntimeAudit pin of zero steady-state recompiles
    across adapter switches (incl. a hot-swap registration mid-audit), and
    the closed-loop load harness (tools/serve_load.py) latency envelope at
    a target RPS over a Zipf adapter mix with heavy-tailed prompts."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    from fedml_tpu.llm.fedllm import lora_init
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from serve_load import run_load

    quick = os.environ.get("FEDML_SERVE_MT_QUICK") == "1"
    slots = 4
    n_adapters = 3 if quick else 32
    n_new = 6 if quick else 24
    n_req = 8 if quick else 64
    buf = 128
    base_cfg = LlamaConfig(vocab_size=258, dim=64, n_layers=2, n_heads=4,
                           n_kv_heads=4, ffn_dim=128, max_seq_len=buf,
                           dtype=jnp.float32, lora_rank=0)
    mt_cfg = dataclasses.replace(base_cfg, lora_rank=8)
    base_model, mt_model = LlamaLM(base_cfg), LlamaLM(mt_cfg)
    dummy = jnp.zeros((1, 8), jnp.int32)
    base_params = base_model.init(jax.random.PRNGKey(0), dummy)["params"]
    variables = mt_model.init(jax.random.PRNGKey(0), dummy)

    result = {"quick": quick, "slots": slots, "adapters": n_adapters,
              "max_new_tokens": n_new, "requests": n_req}

    def _row(name, value):
        result[name] = value
        print(f"[serve-mt-row] {name}={value} t={time.perf_counter():.0f}",
              flush=True)

    mt = ContinuousBatchingEngine(mt_model, variables["params"], slots=slots,
                                  buf_len=buf,
                                  adapter_slots=n_adapters + 2)
    single = ContinuousBatchingEngine(base_model, base_params, slots=slots,
                                      buf_len=buf)
    try:
        names = []
        for i in range(n_adapters):
            name = f"cohort{i}"
            mt.registry.register(name, lora_init(
                jax.random.PRNGKey(100 + i), variables["lora"]))
            names.append(name)

        # warm every compiled program off-clock: adapter + base admission
        # and the batched MT step, plus the plain engine's pair
        mt.generate([5, 17, 42], max_new_tokens=2, adapter=names[0])
        mt.generate([5, 17, 42], max_new_tokens=2)
        single.generate([5, 17, 42], max_new_tokens=2)

        # acceptance pin: adapter switches (every registered adapter +
        # base + a mid-audit hot-swap registration) reuse the ONE program
        with JaxRuntimeAudit() as audit:
            mt.registry.register("hot", lora_init(
                jax.random.PRNGKey(999), variables["lora"]))
            mix = [None, "hot"] + names
            qs = [mt.submit([i + 1, i + 2, i + 3], max_new_tokens=4,
                            adapter=mix[i % len(mix)])
                  for i in range(max(8, len(mix)))]
            for q in qs:
                while q.get(timeout=120) is not None:
                    pass
        _row("steady_state_recompiles", audit.compilations)

        # aggregate tokens/s: the same request battery through the
        # adapter-blind engine (the one-engine-per-adapter world's best
        # case: zero lora math) and the MT engine with requests spread
        # over every adapter
        def agg_tok_s(engine, cycle):
            t0 = time.perf_counter()
            qs = [engine.submit([i + 1, i + 2, i + 3],
                                max_new_tokens=n_new,
                                adapter=cycle[i % len(cycle)])
                  for i in range(n_req)]
            total = 0
            for q in qs:
                while q.get(timeout=300) is not None:
                    total += 1
            return round(total / (time.perf_counter() - t0), 1)

        _row("single_adapter_tok_s", agg_tok_s(single, [None]))
        _row("mt_tok_s", agg_tok_s(mt, names + [None]))
        _row("mt_vs_single_ratio",
             round(result["mt_tok_s"] / result["single_adapter_tok_s"], 3))

        # closed-loop load at target RPS (Zipf adapter mix, heavy-tailed
        # prompt lengths) — p50/p99 latency + queue depth for the BENCH row
        rps = 20.0 if quick else 40.0
        result["load"] = run_load(
            mt, target_rps=rps, n_requests=n_req,
            adapters=[None] + names, max_new_tokens=n_new,
            vocab=base_cfg.vocab_size, seed=0)
        _row("latency_p50_ms", result["load"]["latency_p50_ms"])
        _row("latency_p99_ms", result["load"]["latency_p99_ms"])
        _row("load_tokens_per_s", result["load"]["tokens_per_s"])
        result["registry_stats"] = dict(mt.registry.stats)
        result["serve_stats_requests"] = len(mt.serve_stats["requests"])
    finally:
        mt.stop()
        single.stop()
    return result


def serve_slo_bench() -> dict:
    """fedslo (ISSUE 19): request-lifecycle telemetry under the PR 4
    overhead contract, native-histogram fleet merging, and the SLO
    burn-rate + canary-verdict plane.

    Four acceptance pins land in the BENCH row:

    - telemetry ON ≡ OFF to JaxRuntimeAudit (same compiles / explicit
      transfers on a warm engine) and the tok/s overhead stays small —
      all fedslo measurement is host clocks at pre-existing sync points;
    - a slow-service-rate canary replica (every request holds its slot
      an order of magnitude longer against the same arrival blast, so
      queueing inflates its measured ttft) is a regression the judge must call
      ``rollback``, while an identical replica must ``promote``; both
      verdicts land in a schema-valid JSONL audit trail;
    - two replicas' scraped histograms merged by bucket addition give
      fleet percentiles within one bucket width of the harness's exact
      sample percentiles (tools/serve_load.py --multi path);
    - the engine's own burn-rate windows report ok on clean traffic.

    FEDML_SLO_QUICK=1 shrinks the batteries for the tier-1 smoke."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from fedml_tpu import obs
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    from fedml_tpu.llm.fedllm import lora_init
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.obs.canary import CanaryJudge, validate_audit_log
    from fedml_tpu.obs.histogram import (merge_bucket_entries,
                                         quantile_from_buckets)
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from serve_load import run_fleet

    quick = os.environ.get("FEDML_SLO_QUICK") == "1"
    slots = 4
    n_adapters = 2 if quick else 8
    n_new = 4 if quick else 12
    n_req = 16 if quick else 48
    buf = 128
    rules = [{"name": "serve_ttft_p99",
              "objective": {"metric": "serve_ttft_seconds",
                            "threshold": 30.0, "compliance": 0.99}}]
    cfg = LlamaConfig(vocab_size=258, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=4, ffn_dim=128, max_seq_len=buf,
                      dtype=jnp.float32, lora_rank=8)
    model = LlamaLM(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    params = variables["params"]

    result = {"quick": quick, "slots": slots, "adapters": n_adapters,
              "max_new_tokens": n_new, "requests": n_req}

    def _row(name, value):
        result[name] = value
        print(f"[serve-slo-row] {name}={value} "
              f"t={time.perf_counter():.0f}", flush=True)

    def mk_engine(n_slots, metrics_port=None):
        # the batteries' prompts are three tokens: in the default 64-token
        # chunks the one prefill lane, not the slots, would set every
        # request's first token, and the canary's slower replica (which
        # holds its SLOTS longer) would stand only 2-3x above the baseline
        eng = ContinuousBatchingEngine(
            model, params, slots=n_slots, buf_len=buf,
            adapter_slots=n_adapters + 2, slo_rules=rules,
            metrics_port=metrics_port, prefill_chunk_tokens=8)
        for i in range(n_adapters):
            eng.registry.register(f"cohort{i}", lora_init(
                jax.random.PRNGKey(100 + i), variables["lora"]))
        return eng

    def battery(eng, n, adapters=(None,), new_tokens=None):
        """Blast n requests (all submitted up front) and drain them;
        returns aggregate tok/s.  ttft/e2e land in the engine's own
        histograms via _observe_finish."""
        t0 = time.perf_counter()
        qs = [eng.submit([i + 1, i + 2, i + 3],
                         max_new_tokens=new_tokens or n_new,
                         adapter=adapters[i % len(adapters)])
              for i in range(n)]
        total = 0
        for q in qs:
            while q.get(timeout=300) is not None:
                total += 1
        return round(total / (time.perf_counter() - t0), 1)

    main_eng = mk_engine(slots)
    mix = [None] + [f"cohort{i}" for i in range(n_adapters)]
    try:
        # warm every compiled program off-clock (prefill + batched step,
        # adapter and base admission)
        main_eng.generate([5, 17, 42], max_new_tokens=2,
                          adapter="cohort0")
        main_eng.generate([5, 17, 42], max_new_tokens=2)

        # -- PR 4 overhead contract: telemetry ON ≡ OFF ------------------
        # interleaved median-of-N batteries: on a shared host a single
        # pair confounds telemetry cost with load drift.  Each path gets
        # one unmeasured FULL-SIZE warm battery first — the engine's
        # throughput climbs over its first few batteries (allocator and
        # dispatch caches), and the tracer path additionally pays
        # one-time lazy imports / first-event allocations; neither is
        # steady-state overhead.
        battery(main_eng, n_req, adapters=mix)
        obs.configure(enabled=True, reset=True)
        try:
            battery(main_eng, n_req, adapters=mix)
        finally:
            obs.configure(enabled=False)
        audit_off, audit_on = JaxRuntimeAudit(), JaxRuntimeAudit()
        off_runs, on_runs = [], []

        def measure(tracer_on):
            if not tracer_on:
                with audit_off:
                    off_runs.append(battery(main_eng, n_req,
                                            adapters=mix))
                return
            obs.configure(enabled=True, reset=True)
            try:
                with audit_on:
                    on_runs.append(battery(main_eng, n_req,
                                           adapters=mix))
            finally:
                obs.configure(enabled=False)

        reps = 3 if quick else 5
        for rep in range(reps):
            # alternate which mode goes first: host load drifts, and a
            # fixed order would bill the drift to the tracer
            for tracer_on in ((False, True) if rep % 2 == 0
                              else (True, False)):
                measure(tracer_on)
        tok_s_off = sorted(off_runs)[len(off_runs) // 2]
        tok_s_on = sorted(on_runs)[len(on_runs) // 2]
        _row("steady_state_recompiles",
             audit_off.compilations + audit_on.compilations)
        _row("audit_equal_on_off", int(
            (audit_on.compilations, audit_on.device_puts,
             audit_on.device_gets)
            == (audit_off.compilations, audit_off.device_puts,
                audit_off.device_gets)))
        _row("tok_s_telemetry_off", tok_s_off)
        _row("tok_s_telemetry_on", tok_s_on)
        _row("telemetry_overhead_pct",
             round(100.0 * (tok_s_off - tok_s_on) / max(tok_s_off, 1e-9),
                   2))

        # -- the engine's own burn-rate windows on clean traffic ---------
        slo_eval = main_eng.slo_windows["serve_ttft_p99"].evaluate()
        _row("slo_status", slo_eval["status"])
        result["slo_windows"] = [
            {k: w[k] for k in ("window", "burn_short", "burn_long",
                               "firing")}
            for w in slo_eval["windows"]]

        # headline: ttft p99 off the engine's native histogram (all
        # adapter labels merged)
        ttft_all = merge_bucket_entries(
            list(main_eng.serve_hists.ttft.snapshot().values()))
        _row("serve_ttft_p99_ms", round(
            (quantile_from_buckets(ttft_all, 0.99) or 0.0) * 1e3, 2))
    finally:
        main_eng.stop()

    # -- canary verdicts off per-adapter histogram snapshots -------------
    # baseline and the clean candidate are identical replicas; the
    # degraded candidate replica serves the SAME arrival blast but each
    # request holds its slot an order of magnitude longer (a slower
    # service-rate build) — queueing inflates its measured ttft on any
    # host, parallel or not
    baseline_eng = mk_engine(slots, metrics_port=0)
    clean_eng = mk_engine(slots, metrics_port=0)
    degraded_eng = mk_engine(slots)
    serve_slo: dict = {}
    try:
        # warmed as base traffic (the adapter row is traced: the same two
        # programs): a first token waits for the chunk and the tick
        # program's compilation, and under "cohort0" that one sample would
        # be the p99 the threshold is pegged to
        for eng in (baseline_eng, clean_eng, degraded_eng):
            eng.generate([5, 17, 42], max_new_tokens=2)
        battery(baseline_eng, n_req, adapters=["cohort0"])
        battery(clean_eng, n_req, adapters=["cohort0"])
        battery(degraded_eng, n_req, adapters=["cohort0"],
                new_tokens=min(96, buf - 8))
        base_entry = baseline_eng.serve_hists.ttft.snapshot()["cohort0"]
        clean_entry = clean_eng.serve_hists.ttft.snapshot()["cohort0"]
        deg_entry = degraded_eng.serve_hists.ttft.snapshot()["cohort0"]
        # SLO threshold pegged to the baseline's own p99: an identical
        # replica sits far under it, the 4x-queued replica far over
        thr = 2.0 * (quantile_from_buckets(base_entry, 0.99) or 0.05)
        audit_path = os.path.join(tempfile.mkdtemp(prefix="fedslo_"),
                                  "canary_audit.jsonl")
        judge = CanaryJudge(
            [{"name": "canary_ttft",
              "objective": {"metric": "serve_ttft_seconds",
                            "threshold": thr, "compliance": 0.99}}],
            audit_path=audit_path,
            min_count=min(20, max(5, n_req // 2)))
        promote = judge.judge(base_entry, clean_entry,
                              adapter="clean-replica")
        rollback = judge.judge(base_entry, deg_entry,
                               adapter="degraded-replica")
        records = validate_audit_log(audit_path)
        serve_slo.update(
            threshold_s=round(thr, 4),
            promote_verdict=promote["verdict"],
            rollback_verdict=rollback["verdict"],
            promote_detected=int(promote["verdict"] == "promote"),
            rollback_detected=int(rollback["verdict"] == "rollback"),
            rollback_bad_fraction=rollback["rules"][0]
            ["candidate_bad_fraction"],
            shift_p_value=rollback["shift"]["p_value"],
            audit_records=len(records),
            audit_valid=1)

        # -- fleet merge: two replicas' scrapes vs exact percentiles -----
        fleet = run_fleet(
            [baseline_eng, clean_eng],
            [baseline_eng.metrics_server.url,
             clean_eng.metrics_server.url],
            target_rps=20.0, n_requests=n_req,
            adapters=mix, max_new_tokens=n_new,
            vocab=cfg.vocab_size, seed=0)
        serve_slo.update(
            fleet_merge_ok=int(fleet["merge_ok"]),
            fleet_requests=fleet["fleet_requests"],
            fleet_ttft_p99_ms=fleet["fleet_ttft_p99_ms"],
            merge_checks=fleet["merge_checks"])
    finally:
        baseline_eng.stop()
        clean_eng.stop()
        degraded_eng.stop()
    result["serve_slo"] = serve_slo
    for k in ("promote_verdict", "rollback_verdict", "rollback_detected",
              "fleet_merge_ok"):
        _row(f"serve_slo.{k}", serve_slo[k])
    return result


def main():
    if "--agg" in sys.argv:
        # the scatter-vs-replicated comparison needs a multi-shard mesh;
        # force 8 virtual host-platform devices BEFORE the backend
        # initializes (a no-op for the accelerator platform if one serves
        # >= 8 real chips)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        info = _platform_info(measure_peak=False)
        result = bench_update_sharding()
        result.update({
            "metric": "server_update_scatter_vs_replicated",
            "value": result["scatter_s_per_round"],
            "unit": "s/round",
            "vs_baseline": result["scatter_speedup"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--comms" in sys.argv:
        # like --agg: the collective-precision comparison needs a
        # multi-shard mesh, so force 8 virtual host devices up front
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        info = _platform_info(measure_peak=False)
        result = bench_comms()
        result.update({
            "metric": "collective_precision_bytes_and_time",
            "value": result["int8_bytes_reduction"],
            "unit": "x_bytes_reduction_int8_vs_fp32",
            "vs_baseline": result["bf16_bytes_reduction"],
            "collective_precision": ["fp32", "bf16", "int8"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--mesh2d" in sys.argv:
        # fixed 8-chip count for the 1-D (8,1) vs 2-D (4,2) comparison;
        # force 8 virtual host devices like --agg/--comms
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        info = _platform_info(measure_peak=False)
        result = bench_mesh2d()
        result.update({
            "metric": "mesh2d_client_x_model_layout",
            "value": result["mesh2d_s_per_round"],
            "unit": "s/round",
            "vs_baseline": result["mesh2d_vs_1d_round"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--pipeline" in sys.argv:
        # fixed 8-chip count for the 2-D (4,2) vs 3-D (2,2,2) pipeline
        # comparison; force 8 virtual host devices like --agg/--mesh2d
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        info = _platform_info(measure_peak=False)
        result = bench_pipeline()
        result.update({
            "metric": "mesh3d_pipeline_layout",
            "value": result["mesh3d_s_per_round"],
            "unit": "s/round",
            "vs_baseline": result["mesh3d_vs_2d_round"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--verify" in sys.argv:
        # lowering the mesh programs needs the 8-virtual-device host
        # mesh, like --agg/--comms/--mesh2d
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        info = _platform_info(measure_peak=False)
        result = bench_verify()
        mesh = result["programs"].get("mesh1d_scatter", {})
        result.update({
            "metric": "fedverify_lowering_contract_census",
            "value": result["violations"],
            "unit": "unsuppressed_violations",
            "vs_baseline": mesh.get("census_bytes", {}).get("client"),
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--chaos" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = bench_chaos()
        result.update({
            "metric": "fedguard_chaos_fault_tolerance_matrix",
            "value": result["wallclock_overhead_vs_clean"],
            "unit": "x_wallclock_crash_vs_clean",
            "vs_baseline": result["rounds_completed_under_chaos"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--wire" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = bench_wire()
        result.update({
            "metric": "fedwire_quantized_wire_matrix",
            "value": result["wire_bytes_fp32_over_int8"],
            "unit": "x_measured_wire_bytes_fp32_over_int8",
            "vs_baseline": result["int8_loss_delta_vs_off"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--trace" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = bench_trace()
        result.update({
            "metric": "fedtrace_overhead_and_breakdown",
            "value": result["trace_overhead_pct"],
            "unit": "pct_overhead_traced_vs_untraced",
            "vs_baseline": None,
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--health" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = bench_health()
        result.update({
            "metric": "fedmon_labelflip_detection_and_overhead",
            "value": result["detector_recall"],
            "unit": "recall_at_10pct_flipped",
            "vs_baseline": result["detector_precision"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--store" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = bench_store()
        result.update({
            "metric": "client_store_1m_registered_vs_dense",
            "value": result["store_s_per_round"],
            "unit": "s/round",
            "vs_baseline": result["store_vs_dense_sameshape"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--async" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = bench_async()
        result.update({
            "metric": "fedbuff_vs_sync_wallclock_to_target",
            "value": result["fedbuff_sim_wallclock_to_target_s"],
            "unit": "sim_s_to_target_acc",
            "vs_baseline": result["async_wallclock_speedup"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--population" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = bench_population()
        largest = max(result["sizes"])
        result.update({
            "metric": "population_vmap_vs_sequential_sweep",
            "value": result[f"p{largest}_pop_wallclock_s"],
            "unit": "s_total_wallclock",
            "vs_baseline": result[f"p{largest}_pop_vs_seq"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--fused" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = bench_round_fusion()
        result.update({
            "metric": "fedavg_round_block_fusion",
            "value": result["fused_s_per_round"],
            "unit": "s/round",
            "vs_baseline": result["fused_speedup"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--serve-slo" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = serve_slo_bench()
        result.update({
            "metric": "serve_slo_burn_rate_canary",
            "value": result["serve_ttft_p99_ms"],
            "unit": "ms_ttft_p99_native_histogram",
            "vs_baseline": result["serve_slo"]["rollback_detected"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--serve-mt" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = serve_mt_bench()
        result.update({
            "metric": "serve_mt_multi_tenant_lora",
            "value": result["mt_tok_s"],
            "unit": f"tok_s_aggregate_{result['adapters']}_adapters",
            "vs_baseline": result["mt_vs_single_ratio"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--serve" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = serve_bench(info["platform"] not in ("cpu",))
        batched_rows = {k: v for k, v in result.items()
                        if k.startswith("batched") and "int8" not in k}
        best_row = max(batched_rows, key=batched_rows.get)
        best_batched = batched_rows[best_row]
        result.update({
            "metric": "serving_decode_tokens_per_sec",
            "value": best_batched,
            # provenance: which configuration produced the headline number
            # (horizon variants compete; the winner can shift run-to-run)
            "best_row": best_row,
            "unit": "tok/s_aggregate_4slots",
            "vs_baseline": (round(best_batched / result["plain_tok_s"], 2)
                            if result.get("plain_tok_s") else None),
            **{k: info[k] for k in _HOST_CTX_KEYS},
        })
        print(json.dumps(result))
        return

    if "--attn" in sys.argv:
        info = _platform_info(measure_peak=False)
        result = attn_sweep()
        result.update({k: info[k] for k in _HOST_CTX_KEYS})
        print(json.dumps(result))
        return

    if "--llm-ablate" in sys.argv:
        # MFU ablation grid over the docs/MFU_ROOFLINE.md levers (round-4
        # VERDICT item 2): anchor -> batch 8 -> remat=full -> flash off.
        # Each row is a fresh trace so the flash gate re-evaluates.
        info = _platform_info()
        on_accel = info["platform"] not in ("cpu",)
        rows = {}
        big_b = 8 if on_accel else 4  # 2x the platform's anchor batch
        for name, kw in (
                ("anchor_dots_b4", dict(flash_mode="auto")),
                (f"batch{big_b}_dots", dict(batch=big_b,
                                            flash_mode="auto")),
                ("remat_full_b4", dict(remat="full", flash_mode="auto")),
                ("flash_off_dots_b4", dict(flash_mode="off")),
        ):
            rows[name] = bench_llm_lora(on_accel, info["peak_flops"],
                                        **kw)
        best = max((r for r in rows.values() if r.get("mfu")),
                   key=lambda r: r["mfu"], default=None)
        result = {
            "metric": "llm_lora_mfu_ablation_best",
            "value": best["mfu"] if best else None,
            "unit": "honest_mfu",
            "vs_baseline": (round(best["mfu"] / rows["anchor_dots_b4"]["mfu"],
                                  3)
                            if best and rows["anchor_dots_b4"].get("mfu")
                            else None),
            "rows": rows,
            "peak_flops": info["peak_flops"],
            "peak_flops_source": info["peak_flops_source"],
            **{k: info[k] for k in _HOST_CTX_KEYS},
        }
        print(json.dumps(result))
        return

    info = _platform_info()
    on_accel = info["platform"] not in ("cpu",)
    peak = info["peak_flops"]

    tpu_dt = bench_fedml_tpu()
    try:
        ref_dt = bench_torch_reference_style()
    except ImportError:            # torch is an optional comparison
        ref_dt = None
    llm = bench_llm_lora(on_accel, peak)
    samples_per_round = CLIENTS_PER_ROUND * BATCH * STEPS_PER_CLIENT
    result = {
        "metric": "fedavg_wall_clock_per_round_256clients_mnist_lr",
        "value": round(tpu_dt, 5),
        "unit": "s/round",
        "vs_baseline": round(ref_dt / tpu_dt, 2) if ref_dt else None,
        "samples_per_sec": round(samples_per_round / tpu_dt, 1),
        "ref_torch_cpu_s_per_round": round(ref_dt, 4) if ref_dt else None,
        "fedavg_mfu": (round(fedavg_round_flops() / tpu_dt / peak, 8)
                       if peak else None),
        "llm_lora": llm,
        "platform": info["platform"],
        "device_kind": info["device_kind"],
        "peak_flops": info["peak_flops"],
        "peak_flops_source": info["peak_flops_source"],
        "host_load_avg_1m": info["host_load_avg_1m"],
        "host_load_avg_5m": info["host_load_avg_5m"],
        "host_cpus": info["host_cpus"],
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
