"""fedmon — live federation-health plane (ISSUE 14).

Pinned here:

- detector semantics on SYNTHETIC per-client stat streams: a scaled
  update and a label-flip signature flag exactly the planted clients; a
  benign-heterogeneity stream flags nobody (precision guard);
- the INTEGRATION bar: a 10%-label-flip sp FedAvg run reaches recall
  ≥ 0.9 AND precision ≥ 0.9 by round 10, on the fused block path too,
  and the fedbuff async engine carries the per-slot staleness lane;
- the ZERO-OVERHEAD contract with ``args.health`` on: steady-state
  8-shard scatter mesh rounds (unfused AND fused) and fedbuff async
  applies add ZERO XLA compiles and ZERO explicit host↔device transfers
  vs the health-off run (``JaxRuntimeAudit`` counter equality — the PR 4
  contract extended to the per-client stat rows);
- the Prometheus surface: ``Tracer.export_prometheus`` round-trips
  through a real text-format parser even with names/args containing
  ``.``/``-``/``"``/``\\`` (the satellite fix), and the live endpoint
  serves /metrics · /healthz · /debug/health with the declarative-SLO
  ok→degraded transition;
- ``tools/fedtrace.py health`` renders the offline report from a
  captured trace (flagged clients + trajectories), and
  ``tools/serve_load.py --scrape-metrics`` cross-checks the serving
  gauges against its own measurements.
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu import obs
from fedml_tpu.arguments import load_arguments
from fedml_tpu.obs.health import (DEFAULT_SLO_RULES, HealthConfig,
                                  HealthMonitor, evaluate_slos,
                                  load_slo_rules, robust_z)
from fedml_tpu.obs.metricsd import (MetricsServer, parse_prometheus_text,
                                    prom_value)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "fedtrace.py")

sys.path.insert(0, os.path.join(REPO, "tools"))
import fedtrace  # noqa: E402


@pytest.fixture
def clean_tracer():
    obs.configure(enabled=False)
    obs.get_tracer().reset()
    yield obs.get_tracer()
    obs.configure(enabled=False)
    tr = obs.get_tracer()
    tr.reset()
    tr.path = None
    tr.label = None


# -- detector units on synthetic stat streams --------------------------------

def _benign_stats(rng, n):
    return {
        "update_norm": [rng.lognormvariate(0.0, 0.12) for _ in range(n)],
        "cosine": [0.8 + rng.gauss(0.0, 0.03) for _ in range(n)],
        "loss_delta": [rng.gauss(0.0, 0.05) for _ in range(n)],
        "weight": [1.0] * n,
    }


def test_detector_flags_scaled_update_signature():
    import random
    rng = random.Random(0)
    mon = HealthMonitor()
    for r in range(4):
        stats = _benign_stats(rng, 48)
        stats["update_norm"][7] = 40.0 * (1.0 + 0.1 * r)  # ~40x median
        mon.observe_round(r, list(range(48)), stats)
    assert mon.flagged() == [7]
    (info,) = mon.flag_details()
    assert info["reason"] == "scaled_update"


def test_detector_flags_label_flip_signature_and_staleness_passthrough():
    import random
    rng = random.Random(1)
    mon = HealthMonitor()
    bad = {3, 19}
    for r in range(5):
        stats = _benign_stats(rng, 48)
        stats["staleness"] = [0.0] * 48
        for c in bad:
            stats["cosine"][c] = -0.7 + rng.gauss(0.0, 0.05)
            stats["loss_delta"][c] = 1.4 + rng.gauss(0.0, 0.1)
            stats["staleness"][c] = 2.0
        mon.observe_round(r, list(range(48)), stats)
    assert mon.flagged() == sorted(bad)
    assert all(f["staleness"] == 2.0 for f in mon.flag_details())


def test_detector_benign_heterogeneity_flags_nobody():
    """Precision guard: smooth 4x norm spread + mild cosine/loss noise is
    heterogeneity, not an attack."""
    import random
    rng = random.Random(2)
    mon = HealthMonitor()
    for r in range(8):
        n = 48
        stats = {
            # smooth spread across the cohort, not an outlier
            "update_norm": [0.5 + 1.5 * i / n + rng.lognormvariate(0, 0.2)
                            for i in range(n)],
            "cosine": [0.6 + rng.gauss(0.0, 0.1) for i in range(n)],
            "loss_delta": [rng.gauss(0.0, 0.3) for _ in range(n)],
            "weight": [1.0] * n,
        }
        mon.observe_round(r, list(range(n)), stats)
    assert mon.flagged() == []
    assert mon.gauges()["health.anomaly_rate"] == 0.0


def test_detector_pad_rows_and_unflag_hysteresis():
    """Weight-0 pad rows never enter the statistics; a client whose
    evidence decays unflags."""
    import random
    rng = random.Random(3)
    mon = HealthMonitor(HealthConfig(min_obs=1))
    stats = _benign_stats(rng, 8)
    stats["update_norm"][5] = 1e6       # pad row with absurd stats...
    stats["weight"][5] = 0.0            # ...but weight 0: invisible
    v = mon.observe_round(0, list(range(8)), stats)
    assert v["clients"] == 7 and mon.flagged() == []
    # one-round attacker flags, then decays below clear_score and unflags
    stats = _benign_stats(rng, 8)
    stats["update_norm"][2] = 500.0
    mon.observe_round(1, list(range(8)), stats)
    assert mon.flagged() == [2]
    for r in range(2, 12):
        mon.observe_round(r, list(range(8)), _benign_stats(rng, 8))
    assert mon.flagged() == []


def test_robust_z_floor_blocks_homogeneous_blowup():
    zs = robust_z([1.0, 1.0001, 0.9999, 1.0002, 5.0], floor=0.5)
    assert abs(zs[0]) < 0.01 and zs[4] == pytest.approx(8.0, rel=0.01)


# -- SLO rules ---------------------------------------------------------------

def test_slo_evaluation_ok_degraded_unhealthy_and_yaml(tmp_path):
    rules = [{"name": "rt", "metric": "health.round_time_s",
              "max": 1.0, "crit": 10.0},
             {"name": "q", "metric": "serve.queue_depth", "max": 4}]
    assert evaluate_slos(rules, {"health.round_time_s": 0.5})["status"] \
        == "ok"
    v = evaluate_slos(rules, {"health.round_time_s": 2.0})
    assert v["status"] == "degraded"
    assert [c["status"] for c in v["checks"]] == ["degraded", "skipped"]
    assert evaluate_slos(rules, {"health.round_time_s": 11.0})["status"] \
        == "unhealthy"
    # min-direction rules
    v = evaluate_slos([{"metric": "acc", "min": 0.9, "crit_min": 0.5}],
                      {"acc": 0.4})
    assert v["status"] == "unhealthy"
    # YAML round-trip
    p = tmp_path / "slo.yaml"
    p.write_text("slos:\n  - name: rt\n    metric: health.round_time_s\n"
                 "    max: 1.0\n    crit: 10.0\n")
    loaded = load_slo_rules(str(p))
    assert loaded[0]["metric"] == "health.round_time_s"
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.yaml"
        bad.write_text("slos:\n  - name: no_metric\n")
        load_slo_rules(str(bad))


# -- prometheus text round-trip (satellite 1) --------------------------------

def test_prometheus_dump_round_trips_with_hostile_names(clean_tracer):
    obs.configure(enabled=True, jax_hooks=False)
    tr = clean_tracer
    with tr.span('serve.admit "cohort-1"', cat="serve"):
        pass
    tr.counter('serve.requests.adapter-"x\\y"', 7)
    tr.counter("async.staleness_p99", 3.5)
    text = tr.export_prometheus()
    samples = parse_prometheus_text(text)   # raises on any bad line
    assert prom_value(samples, "fedtrace_counter",
                      name='serve.requests.adapter-"x\\y"') == 7.0
    assert prom_value(samples, "fedtrace_counter",
                      name="async.staleness_p99") == 3.5
    assert prom_value(samples, "fedtrace_span_count",
                      name='serve.admit "cohort-1"') == 1.0
    # every metric name in the dump is prometheus-legal
    import re
    for name, _, _ in samples:
        assert re.match(r"[a-zA-Z_:][a-zA-Z0-9_:]*$", name), name


def test_parse_prometheus_rejects_malformed_lines():
    with pytest.raises(ValueError):
        parse_prometheus_text('bad{name="unterminated} 1\n')
    with pytest.raises(ValueError):
        parse_prometheus_text("no value here\n")


# -- live endpoint -----------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_metrics_endpoint_serves_and_healthz_transitions(clean_tracer):
    """/healthz is ok before any rounds, then transitions to degraded when
    a deliberately tight round-time SLO is violated (the acceptance
    scenario bench.py --health drives live)."""
    import random
    rng = random.Random(0)
    mon = HealthMonitor(slo_rules=[
        {"name": "rt", "metric": "health.round_time_s", "max": 1e-6},
        *DEFAULT_SLO_RULES])
    srv = MetricsServer(monitor=mon)
    srv.start()
    try:
        code, body = _get(srv.url + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        mon.observe_round(0, list(range(8)), _benign_stats(rng, 8),
                          round_time_s=0.25)   # breaches the 1e-6 SLO
        code, body = _get(srv.url + "/healthz")
        v = json.loads(body)
        assert code == 200 and v["status"] == "degraded"
        assert v["checks"][0]["status"] == "degraded"
        code, body = _get(srv.url + "/metrics")
        samples = parse_prometheus_text(body)
        assert prom_value(samples, "fedmon_gauge",
                          name="health.rounds_observed") == 1.0
        code, body = _get(srv.url + "/debug/health")
        assert code == 200 and json.loads(body)["flagged"] == []
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/nope")
        assert e.value.code == 404
    finally:
        srv.close()


def test_healthz_unhealthy_returns_503(clean_tracer):
    mon = HealthMonitor(slo_rules=[
        {"metric": "health.round_time_s", "max": 1e-9, "crit": 1e-6}])
    srv = MetricsServer(monitor=mon)
    srv.start()
    try:
        import random
        mon.observe_round(0, [0, 1], _benign_stats(random.Random(0), 2),
                          round_time_s=1.0)
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/healthz")
        assert e.value.code == 503
        assert json.loads(e.value.read().decode())["status"] == "unhealthy"
    finally:
        srv.close()


# -- engine integration ------------------------------------------------------

def _args_for(rounds=10, **over):
    args = load_arguments()
    args.update(
        dataset="synthetic", num_classes=10, input_shape=(28, 28, 1),
        train_size=4096, test_size=256, model="lr",
        client_num_in_total=64, client_num_per_round=32, comm_round=rounds,
        epochs=1, batch_size=16, learning_rate=0.1, random_seed=7,
        partition_method="homo", frequency_of_the_test=5, health=True,
    )
    args.update(**over)
    return fedml_tpu.init(args)


def _flipped_api(backend, rounds=10, n_flip=6, **over):
    from fedml_tpu import data as data_mod, model as model_mod

    args = _args_for(rounds=rounds, **over)
    dataset, out_dim = data_mod.load(args)
    rng = np.random.default_rng(0)
    flipped = sorted(rng.choice(64, size=n_flip, replace=False).tolist())
    for c in flipped:
        idx = dataset.client_idxs[c]
        dataset.train_y[idx] = (10 - 1) - dataset.train_y[idx]
    model = model_mod.create(args, out_dim)
    if backend == "mesh":
        from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI
        api = MeshFedAvgAPI(args, None, dataset, model)
    elif backend == "fedbuff":
        from fedml_tpu.simulation.async_engine import FedBuffAPI
        api = FedBuffAPI(args, None, dataset, model, client_mode="vmap")
    else:
        from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI
        api = FedAvgAPI(args, None, dataset, model, client_mode="vmap")
    return api, flipped


def _precision_recall(flagged, flipped):
    tp = len(set(flagged) & set(flipped))
    fp = len(set(flagged) - set(flipped))
    return tp / max(tp + fp, 1), tp / max(len(flipped), 1)


def test_label_flip_sp_detected_by_round_10():
    """ISSUE 14 satellite: 10% flipped clients, sp engine — recall ≥ 0.9
    and precision ≥ 0.9 by round 10."""
    api, flipped = _flipped_api("sp", rounds=10)
    api.train()
    precision, recall = _precision_recall(api.health_monitor.flagged(),
                                          flipped)
    assert precision >= 0.9 and recall >= 0.9, (
        api.health_monitor.flagged(), flipped)
    # verdict gauges populated
    g = api.health_monitor.gauges()
    assert g["health.rounds_observed"] == 10.0
    assert g["health.flagged_total"] >= 0.9 * len(flipped)


def test_label_flip_detected_on_fused_block_path():
    """The (K, C) block-stacked stat rows flush one observe per round."""
    api, flipped = _flipped_api("sp", rounds=10, round_block=5,
                                frequency_of_the_test=10 ** 9)
    api.train()
    precision, recall = _precision_recall(api.health_monitor.flagged(),
                                          flipped)
    assert precision >= 0.9 and recall >= 0.9
    assert api.health_monitor.gauges()["health.rounds_observed"] == 10.0


def test_label_flip_detected_on_mesh_scatter():
    api, flipped = _flipped_api("mesh", rounds=10)
    assert api.n_shards == 8 and api.update_sharding == "scatter"
    api.train()
    precision, recall = _precision_recall(api.health_monitor.flagged(),
                                          flipped)
    assert precision >= 0.9 and recall >= 0.9


def test_label_flip_detected_on_fedbuff_with_staleness_lane():
    """Twelve applies of a 32-row buffer fed by three generations in
    flight: 384 arrivals over 64 clients, the sync tests' cohort.  (With 16
    a generation, 192 arrivals, client 2 arrived four times, all by apply 6,
    while a flip still scored at the flag line, 5.4 to 6.0 against 5.5, and
    never again; on other seeds a flipped client never arrived at all.  A
    client that does not arrive cannot be flagged: the population has to
    carry the statement.)  Each row is standardised within the generation
    it was measured against (``cohort_robust_z``): pooled over the buffer,
    a minority generation's benign rows read as direction outliers."""
    api, flipped = _flipped_api(
        "fedbuff", rounds=12, federated_optimizer="fedbuff",
        client_num_per_round=32, async_buffer_k=32,
        async_latency_median_s=5.0, async_latency_sigma=1.2,
        async_inflight_gens=3, frequency_of_the_test=4)
    api.train()
    mon = api.health_monitor
    assert min(mon._clients[c].obs for c in flipped) >= 3
    precision, recall = _precision_recall(mon.flagged(), flipped)
    assert precision >= 0.9 and recall >= 0.9, (mon.flagged(), flipped)
    # real staleness flowed through the buffer's tau lane into the gauges
    assert api.health_monitor.gauges()["health.staleness_p99"] >= 1.0


@pytest.mark.parametrize("pad", [0, 3])
def test_cohort_robust_z_is_the_monitors_robust_z(pad):
    """The in-trace standardisation of a generation equals the monitor's
    own ``robust_z`` over the real rows; pad rows read 0."""
    import jax.numpy as jnp
    from fedml_tpu.core import federated
    from fedml_tpu.obs.health import robust_z
    rng = np.random.default_rng(pad)
    vals = rng.normal(0.5, 0.2, 11 + pad).astype(np.float32)
    vals[4] = -0.6
    w = np.r_[np.ones(11, np.float32), np.zeros(pad, np.float32)]
    got = np.asarray(federated.cohort_robust_z(jnp.asarray(vals), w, 0.08))
    np.testing.assert_allclose(got[:11], robust_z(vals[:11].tolist(), 0.08),
                               rtol=1e-5, atol=1e-5)
    assert not got[11:].any() and got[:11].argmin() == 4 and got[4] < -3


def test_monitor_takes_a_generations_own_z_lanes():
    """A buffer of two generations: five rows measured against a mean that
    flipped members pulled (benign cosine -0.2) beside eleven of a clean
    generation (0.75).  Pooled, the five are direction outliers; with the
    lanes the engine standardised per generation nobody is."""
    ids = list(range(16))
    cos = [-0.2] * 5 + [0.75] * 11
    base = {"update_norm": [1.0] * 16, "cosine": cos,
            "loss_delta": [0.0] * 16, "weight": [1.0] * 16}
    pooled, lanes = HealthMonitor(), HealthMonitor()
    for r in range(3):
        pooled.observe_round(r, ids, base)
        lanes.observe_round(r, ids, dict(base, z_cosine=[0.0] * 16,
                                         z_loss_delta=[0.0] * 16))
    assert pooled.flagged() == [0, 1, 2, 3, 4]
    assert lanes.flagged() == []


def test_health_population_rejected_early():
    with pytest.raises(ValueError, match="health"):
        _args_for(population=4)


# -- the zero-overhead contract ----------------------------------------------

def _make_mesh_api(health, rounds=6, **over):
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.simulation.mesh.mesh_simulator import MeshFedAvgAPI

    args = _args_for(rounds=rounds, health=health,
                     frequency_of_the_test=10 ** 9, async_staging=False,
                     **over)
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    return MeshFedAvgAPI(args, None, dataset, model)


def _audit_mesh_unfused(health):
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit

    api = _make_mesh_api(health)
    assert api.n_shards == 8 and api.update_sharding == "scatter"
    api.train_one_round(0)
    api.train_one_round(1)
    with JaxRuntimeAudit() as audit:
        for r in (2, 3, 4):
            api.train_one_round(r)
    return audit


def test_health_mesh_rounds_add_zero_compiles_and_syncs(clean_tracer):
    """ISSUE 14 acceptance: health on, the steady-state 8-shard scatter
    mesh round shows ZERO additional compiles and ZERO additional
    explicit host↔device transfers vs the health-off run."""
    base = _audit_mesh_unfused(health=False)
    withh = _audit_mesh_unfused(health=True)
    assert base.compilations == 0, base.compiled
    assert withh.compilations == 0, withh.compiled
    assert withh.device_puts == base.device_puts
    assert withh.device_gets == base.device_gets


def _audit_mesh_fused(health):
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit

    api = _make_mesh_api(health, rounds=12, round_block=4)
    api.train_block(0)
    api.train_block(4)
    with JaxRuntimeAudit() as audit:
        api.train_block(8)
    return audit


def test_health_fused_block_adds_zero_compiles_and_syncs(clean_tracer):
    base = _audit_mesh_fused(health=False)
    withh = _audit_mesh_fused(health=True)
    assert base.compilations == 0, base.compiled
    assert withh.compilations == 0, withh.compiled
    assert withh.device_puts == base.device_puts
    assert withh.device_gets == base.device_gets


def _audit_sp(health):
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    args = _args_for(rounds=6, health=health,
                     frequency_of_the_test=10 ** 9, async_staging=False)
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    api = FedAvgAPI(args, None, dataset, model, client_mode="vmap")
    api.train_one_round(0)
    api.train_one_round(1)
    with JaxRuntimeAudit() as audit:
        for r in (2, 3, 4):
            api.train_one_round(r)
    return audit


def test_health_sp_rounds_add_zero_compiles_and_syncs(clean_tracer):
    base = _audit_sp(health=False)
    withh = _audit_sp(health=True)
    assert base.compilations == 0, base.compiled
    assert withh.compilations == 0, withh.compiled
    assert withh.device_puts == base.device_puts
    assert withh.device_gets == base.device_gets


def _audit_fedbuff(health):
    from fedml_tpu import data as data_mod, model as model_mod
    from fedml_tpu.analysis.runtime import JaxRuntimeAudit
    from fedml_tpu.simulation.async_engine import FedBuffAPI

    args = _args_for(rounds=10, health=health,
                     federated_optimizer="fedbuff",
                     client_num_per_round=16, async_buffer_k=16,
                     async_latency_median_s=5.0, async_latency_sigma=1.2,
                     async_inflight_gens=2, frequency_of_the_test=10 ** 9,
                     async_staging=False)
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    api = FedBuffAPI(args, None, dataset, model, client_mode="vmap")
    for r in (0, 1, 2, 3):
        api.train_one_round(r)
    with JaxRuntimeAudit() as audit:
        for r in (4, 5, 6):
            api.train_one_round(r)
    return audit


def test_health_fedbuff_steady_state_zero_compiles(clean_tracer):
    base = _audit_fedbuff(health=False)
    withh = _audit_fedbuff(health=True)
    assert base.compilations == 0, base.compiled
    assert withh.compilations == 0, withh.compiled
    assert withh.device_puts == base.device_puts
    assert withh.device_gets == base.device_gets


# -- trace plane + offline report --------------------------------------------

def test_health_counters_and_offline_report(clean_tracer, tmp_path):
    """A traced health run leaves health.verdict spans + health.* counters
    in the capture; fedtrace health renders the offline report naming the
    flagged clients."""
    obs.configure(enabled=True, reset=True)
    api, flipped = _flipped_api("sp", rounds=10, trace=True)
    api.train()
    path = str(tmp_path / "health_trace.json")
    obs.get_tracer().export_chrome(path)
    obs.configure(enabled=False)

    trace = fedtrace.load_trace(path)
    assert fedtrace.validate_events(trace["traceEvents"]) == []
    h = fedtrace.health_report(trace)
    assert h["rounds_observed"] == 10
    precision, recall = _precision_recall(h["flagged_clients"], flipped)
    assert precision >= 0.9 and recall >= 0.9
    assert h["anomaly_rate_max"] > 0
    # CLI contract
    out = subprocess.run([sys.executable, CLI, "health", path, "--json"],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout)["flagged_clients"] == \
        h["flagged_clients"]
    # a healthless trace is a clean error, exit 1
    empty = str(tmp_path / "empty.json")
    with open(empty, "w") as fh:
        json.dump({"traceEvents": []}, fh)
    out = subprocess.run([sys.executable, CLI, "health", empty],
                         capture_output=True, text=True)
    assert out.returncode == 1 and "fedmon" in out.stderr


# -- serve_load scrape cross-check -------------------------------------------

@pytest.mark.slow
def test_serve_load_scrape_agrees_with_harness(clean_tracer):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.llm.fedllm import lora_init
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.serving.batching import ContinuousBatchingEngine
    from serve_load import run_load

    obs.configure(enabled=True, reset=True)
    buf_len = 64
    cfg = LlamaConfig(vocab_size=258, dim=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=buf_len,
                      dtype=jnp.float32, lora_rank=4)
    model = LlamaLM(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    engine = ContinuousBatchingEngine(
        model, variables["params"], slots=2, buf_len=buf_len,
        adapter_slots=3, metrics_port=0)
    assert engine.metrics_server is not None
    try:
        engine.registry.register(
            "a0", lora_init(jax.random.PRNGKey(1), variables["lora"]))
        engine.generate([5, 17], max_new_tokens=2, adapter="a0")  # warm
        report = run_load(engine, target_rps=24.0, n_requests=48,
                          adapters=[None, "a0"], max_new_tokens=16,
                          vocab=cfg.vocab_size, seed=0,
                          scrape_url=engine.metrics_server.url)
    finally:
        engine.stop()
    assert engine.metrics_server is None  # stop() closed it
    assert report["scrape"]["ok"], report["scrape"]
    assert report["completed"] == 48
