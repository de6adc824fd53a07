"""Pins for the bench/capture tooling invariants.

These guard the measurement infrastructure itself (bench.py ablate grid,
the regress gate, the quick modes), not the framework — a corrupted
capture pipeline silently poisons every committed perf number.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _import_bench():
    sys.path.insert(0, REPO)
    import bench
    return bench


def test_bench_llm_lora_restores_flash_mode_env(monkeypatch):
    """flash_mode must be visible to the traces the call makes and be
    restored afterward — on success AND when the impl raises (a leaked
    "off" would silently corrupt the next same-process measurement)."""
    bench = _import_bench()
    seen = {}

    def fake_impl(on_accel, peak, batch, remat, flash_mode):
        seen["env"] = os.environ.get("FEDML_TPU_FLASH_MODE")
        if flash_mode == "boom":
            raise RuntimeError("impl failed")
        return {"mfu": 1.0}

    monkeypatch.setattr(bench, "_bench_llm_lora_impl", fake_impl)

    monkeypatch.setenv("FEDML_TPU_FLASH_MODE", "auto")
    out = bench.bench_llm_lora(False, None, flash_mode="off")
    assert out == {"mfu": 1.0}
    assert seen["env"] == "off"
    assert os.environ["FEDML_TPU_FLASH_MODE"] == "auto"  # restored

    monkeypatch.delenv("FEDML_TPU_FLASH_MODE")
    with pytest.raises(RuntimeError):
        bench.bench_llm_lora(False, None, flash_mode="boom")
    assert "FEDML_TPU_FLASH_MODE" not in os.environ  # restored to absent

    # no override -> env untouched
    bench.bench_llm_lora(False, None)
    assert "FEDML_TPU_FLASH_MODE" not in os.environ


def test_bench_update_sharding_quick(monkeypatch):
    """bench.py --agg smoke: the scatter-vs-replicated comparison runs green
    on the 8-virtual-device mesh and reports both modes' wall-clock (tier-1
    exercises the scatter path end-to-end through the bench harness)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_AGG_QUICK", "1")
    out = bench.bench_update_sharding()
    assert out["quick"] is True
    assert out["n_shards"] == 8
    assert out["scatter_s_per_round"] > 0
    assert out["replicated_s_per_round"] > 0
    assert out["scatter_speedup"] > 0


def test_bench_round_fusion_quick(monkeypatch):
    """bench.py --fused smoke: the K=8 fused round-block runs green through
    the bench harness and reports both dispatch modes' wall-clock plus the
    round_block provenance field (tier-1 exercises the fused scan path
    end-to-end; the >=1.2x acceptance number comes from the full-size
    run, not this trimmed cohort)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_FUSED_QUICK", "1")
    out = bench.bench_round_fusion()
    assert out["quick"] is True
    assert out["round_block"] == 8
    assert out["unfused_s_per_round"] > 0
    assert out["fused_s_per_round"] > 0
    assert out["fused_speedup"] > 0


def test_bench_population_quick(monkeypatch):
    """bench.py --population smoke: the vmapped-population-vs-sequential
    sweep comparison runs green through the bench harness (tier-1
    exercises the population round end-to-end; the <=0.5x P=16 acceptance
    number comes from the full-size run, not this trimmed cohort)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_POPULATION_QUICK", "1")
    out = bench.bench_population()
    assert out["quick"] is True
    assert out["sizes"] == [1, 2]
    for p in (1, 2):
        assert out[f"p{p}_pop_wallclock_s"] > 0
        assert out[f"p{p}_seq_wallclock_s"] > 0
        assert out[f"p{p}_steady_s_per_round_per_config"] > 0
    # amortization direction: per-config steady-state cost must shrink
    # as members share the dispatch
    assert out["p2_steady_s_per_round_per_config"] < \
        out["p1_steady_s_per_round"] * 1.1


def test_bench_comms_quick(monkeypatch):
    """bench.py --comms smoke: the collective-precision comparison runs
    green on the 8-virtual-device scatter mesh and reports the modeled
    interconnect bytes each precision moves (read back from the round's
    own ObsCarry record) — the byte ratios are cohort-size-independent,
    so the acceptance numbers hold even in this trimmed config; the
    s/round acceptance comes from the full-size run."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_COMMS_QUICK", "1")
    out = bench.bench_comms()
    assert out["quick"] is True
    assert out["n_shards"] == 8
    for p in ("fp32", "bf16", "int8"):
        assert out[f"{p}_s_per_round"] > 0
        assert out[f"{p}_bytes_per_round"] > 0
    # modeled wire bytes: bf16 halves fp32 exactly; int8 ~3.9x (q bytes +
    # per-256-chunk f32 scales)
    assert out["bf16_bytes_reduction"] >= 1.9
    assert out["int8_bytes_reduction"] >= 3.5
    # quantization really happened (residual norm is 0 only at fp32)
    assert out["fp32_quant_error_norm"] == 0.0
    assert out["bf16_quant_error_norm"] > 0
    assert out["int8_quant_error_norm"] > out["bf16_quant_error_norm"]


def test_bench_serve_mt_quick(monkeypatch):
    """bench.py --serve-mt smoke: the multi-tenant LoRA serving benchmark
    runs green — N adapters + base through ONE engine with zero
    steady-state recompiles across adapter switches, an adapter-blind
    baseline ratio, and the closed-loop load harness envelope (the
    >=0.8x / N>=32 acceptance numbers come from the full-size run, not
    this trimmed battery)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_SERVE_MT_QUICK", "1")
    out = bench.serve_mt_bench()
    assert out["quick"] is True
    assert out["adapters"] == 3
    assert out["steady_state_recompiles"] == 0
    assert out["single_adapter_tok_s"] > 0
    assert out["mt_tok_s"] > 0
    assert out["mt_vs_single_ratio"] > 0
    load = out["load"]
    assert load["completed"] == load["requests"] and load["failed"] == 0
    assert load["latency_p99_ms"] >= load["latency_p50_ms"] > 0
    assert load["tokens_per_s"] > 0


def test_bench_serve_slo_quick(monkeypatch):
    """FEDML_SLO_QUICK smoke (fedslo, docs/OBSERVABILITY.md): bench.py
    --serve-slo runs the serving-SLO plane green end-to-end — telemetry
    on ≡ off under JaxRuntimeAudit with zero steady-state recompiles,
    burn-rate windows ok on clean traffic, the CanaryJudge promoting the
    clean candidate AND rolling back the service-time-degraded one, and
    the two-engine fleet's merged native histograms agreeing with exact
    sample quantiles within one bucket width (the ≤2% overhead
    acceptance number comes from the full-size BENCH_r15 run — the
    trimmed battery is too short to measure it)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_SLO_QUICK", "1")
    out = bench.serve_slo_bench()
    assert out["quick"] is True
    assert out["steady_state_recompiles"] == 0
    assert out["audit_equal_on_off"] == 1
    assert out["tok_s_telemetry_off"] > 0
    assert out["tok_s_telemetry_on"] > 0
    assert out["slo_status"] == "ok"
    assert out["serve_ttft_p99_ms"] > 0
    slo = out["serve_slo"]
    assert slo["promote_verdict"] == "promote"
    assert slo["rollback_verdict"] == "rollback"
    assert slo["rollback_detected"] == 1
    assert slo["rollback_bad_fraction"] > 0
    assert slo["audit_records"] == 2 and slo["audit_valid"] == 1
    assert slo["fleet_merge_ok"] == 1
    assert all(slo["merge_checks"].values())


def test_bench_health_quick(monkeypatch):
    """FEDML_HEALTH_QUICK smoke (ISSUE 14): bench.py --health runs the
    fedmon plane green end-to-end — label-flip detection verdict on a
    short run, live /metrics scraped mid-run, the deliberately violated
    straggler SLO driving /healthz ok→degraded, and the offline
    fedtrace-health report agreeing with the live monitor (the ≥0.9
    precision/recall + ≤3% overhead acceptance numbers come from the
    full-size BENCH_r11 run; quick still pins detection on its trimmed
    cohort because the signature is scale-free)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_HEALTH_QUICK", "1")
    out = bench.bench_health()
    assert out["quick"] is True
    assert out["plain_s_per_round"] > 0
    assert out["health_s_per_round"] > 0
    assert out["detector_precision"] >= 0.9
    assert out["detector_recall"] >= 0.9
    assert out["healthz_before"] == "ok"
    assert out["healthz_after"] == "degraded"
    assert out["healthz_transition_ok"] is True
    assert out["mid_run_scrape"].get("rounds_observed", 0) >= 1
    assert out["offline_report_flagged_matches"] is True
    assert out["health_gauges"]["health.rounds_observed"] == \
        out["detection_rounds"]


def test_bench_async_quick(monkeypatch):
    """bench.py --async smoke: fedbuff vs sync FedAvg under the shared
    heavy-tailed latency model runs green — both engines reach the (easy
    quick-mode) target accuracy, the sim-wall-clock speedup is reported,
    and steady state is pinned at zero recompiles with buffer occupancy
    and staleness varying as traced data (the >=1x full-size headline
    comes from BENCH_r10, not this trimmed cohort)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_ASYNC_QUICK", "1")
    out = bench.bench_async()
    assert out["quick"] is True
    assert out["buffer_k"] == out["cohort"] == 8
    assert out["sync_rounds_to_target"] is not None
    assert out["fedbuff_applies_to_target"] is not None
    assert out["sync_sim_wallclock_to_target_s"] > 0
    assert out["fedbuff_sim_wallclock_to_target_s"] > 0
    # the lockstep round is gated by its straggler; arrivals are not
    assert out["async_wallclock_speedup"] > 1.0
    assert out["steady_compiles_async"] == 0
    assert out["fedbuff_steady_host_s_per_apply"] > 0


def test_bench_chaos_quick(monkeypatch):
    """bench.py --chaos smoke (fedguard, docs/FAULT_TOLERANCE.md): the
    four-scenario fault-tolerance matrix runs green on the real
    multi-rank driver — clean parity vs the in-process API, every round
    completed at quorum with one silo crashed, the partition heals, a
    killed-and-restarted rank 0 resumes from the WAL with zero
    double-applied rounds, and the quorum-padded combine never
    recompiles."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_CHAOS_QUICK", "1")
    out = bench.bench_chaos()
    assert out["quick"] is True
    rounds = out["rounds"]
    # crash-one-silo: completes EVERY round, at full strength before the
    # crash and at quorum 2/3 from the crash round on
    assert out["rounds_completed_under_chaos"] == rounds
    traj = out["crash_quorum_trajectory"]
    assert traj[0] == 3 and traj[-1] == 2 and min(traj) >= out["quorum"]
    assert out["crash_loss_delta_vs_clean"] < 0.25
    # clean distributed run == in-process hierarchical math (the wire
    # adds serialization, not math; quick-mode rounds keep drift tiny)
    assert out["wire_vs_inprocess_loss_delta"] < 1e-2
    # partition-and-heal: dips to quorum inside the window, heals after
    assert out["partition_rounds_completed"] == rounds
    assert min(out["partition_quorum_trajectory"]) == out["quorum"]
    assert out["partition_healed"] is True
    # kill-and-restart rank 0: WAL covers every round exactly once
    assert out["kill_rank0_double_applied"] == 0
    assert sorted(out["kill_rank0_wal_rounds"]) == list(range(rounds))
    assert out["kill_rank0_resumed_rounds"][0] == out["crash_round"]
    # quorum closes pad with zero partials — one compiled combine shape
    assert out["steady_compiles_quorum"] == 0


def test_bench_verify_quick(monkeypatch):
    """bench.py --verify smoke: the fedverify census row runs green —
    programs lower+compile, zero unsuppressed contract violations, and
    the row carries the census fields (collectives, bytes vs the
    ObsCarry model, per-chip HBM vs the estimator, signature counts)
    the BENCH json archives (ISSUE 10; docs/FEDVERIFY.md)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_VERIFY_QUICK", "1")
    out = bench.bench_verify()
    assert out["quick"] is True
    assert out["violations"] == 0
    progs = out["programs"]
    assert set(progs) == {"sp_round", "mesh1d_scatter",
                          "serving_paged_prefill_chunk"}
    mesh = progs["mesh1d_scatter"]
    assert mesh["num_partitions"] == 8
    assert mesh["collectives"]["reduce-scatter.client"] == 1
    assert mesh["census_bytes"]["client"] > 0
    assert mesh["modeled_bytes"]["client"] > 0
    assert 0 < mesh["hbm_per_chip"] <= mesh["hbm_estimate"]
    assert mesh["distinct_signatures"] == 1
    # single-partition programs carry no collectives
    assert progs["sp_round"]["collectives"] == {}
    assert progs["sp_round"]["num_partitions"] == 1


def test_bench_mesh2d_quick(monkeypatch):
    """bench.py --mesh2d smoke: the 1-D (8,1) vs 2-D (4,2) comparison runs
    green at a fixed 8-chip count, the per-axis ObsCarry byte split is
    plumbed through (model-axis bytes appear exactly on the 2-D layout),
    layout parity is visible in the round-1 losses, and the LLM_SCALE row
    names a model that fits the 2-D layout but exceeds one chip on 1-D
    (ISSUE 6 acceptance; docs/MESH_2D.md)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_MESH2D_QUICK", "1")
    out = bench.bench_mesh2d()
    assert out["quick"] is True
    assert out["mesh1d_shape"] == [8, 1]
    assert out["mesh2d_shape"] == [4, 2]
    assert out["mesh1d_s_per_round"] > 0
    assert out["mesh2d_s_per_round"] > 0
    # client-axis merge payload is layout-independent; model-axis traffic
    # exists exactly on the 2-D layout
    assert out["mesh2d_client_bytes_per_round"] == \
        out["mesh1d_client_bytes_per_round"] > 0
    assert out["mesh1d_model_bytes_per_round"] == 0
    assert out["mesh2d_model_bytes_per_round"] > 0
    # same seed, same cohort: the layouts train the same model
    assert abs(out["mesh1d_round1_loss"] - out["mesh2d_round1_loss"]) < 2e-5
    ls = out["llm_scale"]
    assert ls["mesh1d_fits"] is False and ls["mesh2d_fits"] is True
    assert ls["n_params"] >= 1e9          # a >=1B model the 1-D mesh cannot run
    assert ls["mesh1d_per_chip_gib"] > ls["hbm_per_chip_gib"]
    assert ls["mesh2d_per_chip_gib"] <= ls["hbm_per_chip_gib"]


def test_bench_pipeline_quick(monkeypatch):
    """bench.py --pipeline smoke: the 2-D (4,2) vs 3-D (2,2,2) pipeline
    comparison runs green at a fixed 8-chip count, the THREE-way per-axis
    ObsCarry byte split is plumbed through (stage-axis bytes appear
    exactly on the pipeline layout; the client-axis merge payload is
    layout-independent), layout parity is visible in the round-1 losses,
    and the LLM_SCALE row's estimator-picked (c, s, m) per-chip HBM
    beats the best (c, m) at equal chips (ISSUE 18 acceptance;
    docs/PIPELINE.md)."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_PIPE_QUICK", "1")
    out = bench.bench_pipeline()
    assert out["quick"] is True
    assert out["mesh2d_shape"] == [4, 1, 2]
    assert out["mesh3d_shape"] == [2, 2, 2]
    assert out["mesh2d_s_per_round"] > 0
    assert out["mesh3d_s_per_round"] > 0
    # client-axis merge payload is layout-independent; stage-axis traffic
    # (the microbatched ppermute ring) exists exactly on the 3-D layout
    assert out["mesh3d_client_bytes_per_round"] == \
        out["mesh2d_client_bytes_per_round"] > 0
    assert out["mesh2d_stage_bytes_per_round"] == 0
    assert out["mesh3d_stage_bytes_per_round"] > 0
    assert out["mesh3d_model_bytes_per_round"] > 0
    # same seed, same cohort: microbatched pipeline trains the same model
    assert abs(out["mesh2d_round1_loss"] - out["mesh3d_round1_loss"]) < 2e-5
    ls = out["llm_scale"]
    assert len(ls["mesh3d_shape"]) == 3 and ls["mesh3d_shape"][1] > 1
    assert ls["mesh3d_fits"] is True
    # the scale unlock: the stage axis lands UNDER the best 2-D per-chip
    # total at the same 8 chips for the 98%-staged 1B model
    assert ls["mesh3d_per_chip_gib"] < ls["mesh2d_per_chip_gib"]
    assert ls["mesh3d_vs_2d_per_chip"] < 1.0


def test_bench_wire_quick(monkeypatch):
    """FEDML_WIRE_QUICK smoke (docs/WIRE.md): bench.py --wire runs the
    fedwire matrix green on the real two-tier driver — measured wire
    bytes drop ~4x int8 vs fp32 (byte ratios are round-count-independent,
    so the acceptance direction holds in this trimmed run), parity stays
    inside the PR 5 tolerances, the chunked bandwidth-capped variant
    completes every round, and the codec adds zero steady-state
    recompiles."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_WIRE_QUICK", "1")
    out = bench.bench_wire()
    assert out["quick"] is True
    assert out["rounds"] == 3 and out["num_silos"] == 2
    assert out["wire_bytes_fp32_over_int8"] > 3.0
    assert out["wire_bytes_off_over_int8"] > 3.0
    assert out["int8_loss_delta_vs_off"] < 1e-2
    assert out["bf16_loss_delta_vs_off"] < 2e-3
    assert out["steady_compiles_wire"] == 0
    assert out["capped_rounds_completed"] == 3
    rows = out["variants"]
    for name in ("off", "fp32", "bf16", "int8", "int8_overlap",
                 "int8_chunk_cap"):
        assert rows[name]["silo_server_bytes"] > 0, name
    # the capped variant really streamed frames on reliable delivery
    assert rows["int8_chunk_cap"]["chunks_sent"] > 0
    # measured-vs-modeled census agreement (the fedtrace headline)
    for name in ("fp32", "bf16", "int8"):
        assert 1.1 < rows[name]["wire_bytes_ratio"] < 1.6, name


def test_fedtrace_regress_smoke(tmp_path, monkeypatch):
    """FEDML_TRACE_REGRESS smoke (ISSUE 11): the perf-regression gate
    runs green over the committed BENCH trajectory + tolerance bands,
    and a mutated (slowed) row makes it exit nonzero — the tier-1 wire
    that stops a PR from silently regressing a pinned headline."""
    import subprocess

    monkeypatch.setenv("FEDML_TRACE_REGRESS", "1")
    cli = os.path.join(REPO, "tools", "fedtrace.py")

    def run(*args):
        return subprocess.run([sys.executable, cli, "regress", *args],
                              cwd=REPO, capture_output=True, text=True)

    # every committed row passes its own bands (rows of other archetypes
    # skip bands whose metric they don't carry)
    import glob

    for row_path in sorted(glob.glob(os.path.join(REPO,
                                                  "BENCH_r*.json"))):
        r = run(row_path, "--json")
        assert r.returncode == 0, (row_path, r.stdout, r.stderr)
        out = json.loads(r.stdout)
        assert out["ok"], row_path
    # at least one band actually fired somewhere in the trajectory
    checked_total = sum(
        json.loads(run(p, "--json").stdout)["checked"]
        for p in glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    assert checked_total >= 4

    # a slowed headline must FAIL the gate with the distinct exit code
    with open(os.path.join(REPO, "BENCH_r06.json")) as fh:
        row = json.load(fh)
    row["p4_steady_s_per_round_per_config"] *= 3.0      # 3x slower
    bad = tmp_path / "slowed.json"
    bad.write_text(json.dumps(row))
    r = run(str(bad), "--baseline-dir", REPO, "--json")
    assert r.returncode == 3, r.stdout
    out = json.loads(r.stdout)
    assert [x["metric"] for x in out["regressions"]] == [
        "p4_steady_s_per_round_per_config"]


def test_bench_trace_records_device_phase_deltas(monkeypatch):
    """bench.py --trace (quick) archives the fedscope measured-vs-modeled
    device-phase deltas and the regress verdict into the BENCH row."""
    bench = _import_bench()
    monkeypatch.setenv("FEDML_TRACE_QUICK", "1")
    out = bench.bench_trace()
    assert out["device_phase_source"] == "measured"
    assert set(out["device_phase_delta"]) == {
        "gather", "client_steps", "merge", "server_update"}
    # shares: deltas sum to ~0 (both sides are normalized shares)
    assert abs(sum(out["device_phase_delta"].values())) < 1e-3
    assert all(v > 0 for v in out["device_phases_measured_s"].values())
    assert out["regress"]["ok"] is True


def test_pytest_shard_partition_deterministic():
    """Shard assignment must be a pure function of the file SET — glob
    returns filesystem-dependent order and `-p no:randomly` runs must
    reproduce the same shards, or a flake 'moves' between workers and
    becomes unreproducible."""
    import random

    import pytest_shard as ps

    files = [f"tests/test_{n}.py" for n in
             ["llm", "mesh", "algorithms", "xent", "comm", "flow",
              "chaos", "moe", "pipeline", "zzz_unknown", "aaa_unknown"]]
    base = ps.partition(list(files), 4)
    rng = random.Random(0)
    for _ in range(10):
        shuffled = list(files)
        rng.shuffle(shuffled)
        assert ps.partition(shuffled, 4) == base

    # every file lands in exactly one shard
    flat = [f for s in base for f in s]
    assert sorted(flat) == sorted(files)

    # equal-weight ties (both unknown files) break on basename, not on
    # input order: aaa before zzz in the greedy sequence
    seq = sorted(files, key=lambda f: (-ps.WEIGHTS.get(
        os.path.basename(f), ps.DEFAULT_WEIGHT), os.path.basename(f)))
    aaa = seq.index("tests/test_aaa_unknown.py")
    zzz = seq.index("tests/test_zzz_unknown.py")
    assert aaa < zzz

    # n > files: empty shards dropped, still deterministic
    tiny = ps.partition(files[:2], 8)
    assert len(tiny) == 2 and ps.partition(files[1::-1], 8) == tiny


def test_serve_quick_filter_keeps_kvint8_and_a_headline_row():
    """The quick-mode trim must keep the dense baseline, a horizon row
    (headline eligible: best_row excludes int8 weights), and the KV-int8
    bandwidth lever — dropping only the int8-WEIGHT engine variants."""
    names = ["batched_tok_s", "batched_int8_tok_s", "batched_h16_tok_s",
             "batched_h16_int8_tok_s", "batched_h16_kvint8_tok_s"]
    kept = [n for n in names if "_int8" not in n or "kvint8" in n]
    assert kept == ["batched_tok_s", "batched_h16_tok_s",
                    "batched_h16_kvint8_tok_s"]
    headline_eligible = [n for n in kept
                         if n.startswith("batched") and "int8" not in n]
    assert headline_eligible  # main()'s max() never sees an empty dict


def test_fedproto_cli_smoke(tmp_path):
    """FEDML_PROTO_QUICK smoke (ISSUE 12): the fedproto CLI contract —
    `check --json` exits 0 with every family extracted, an
    `--update-manifest` round-trip to a fresh path reproduces the
    committed pin byte-for-byte, a tampered manifest exits 1, and bad
    usage exits 2.  Pure stdlib (no jax import in the CLI)."""
    import subprocess

    cli = os.path.join(REPO, "tools", "fedproto.py")

    r = subprocess.run([sys.executable, cli, "check", "--json"], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert [f for f in payload["findings"] if not f["suppressed"]] == []
    committed = json.load(open(os.path.join(
        REPO, "tests", "data", "fedproto", "protocols.json")))
    assert set(payload["families"]) == set(committed["families"])

    # --update-manifest round-trip: fresh pin == committed pin
    fresh = str(tmp_path / "protocols.json")
    r = subprocess.run([sys.executable, cli, "check", "--manifest", fresh,
                        "--update-manifest"], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.load(open(fresh))
    assert got["families"] == committed["families"]

    # tampered pin = reviewed-diff failure (exit 1, manifest-drift named)
    got["families"]["secagg"]["handlers"]["server"].pop("7")
    with open(fresh, "w") as fh:
        json.dump(got, fh)
    r = subprocess.run([sys.executable, cli, "check", "--manifest", fresh],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 1 and "manifest-drift" in r.stdout

    # usage errors exit 2
    r = subprocess.run([sys.executable, cli], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 2
    r = subprocess.run([sys.executable, cli, "check", "--families",
                        "no-such-family"], cwd=REPO, capture_output=True,
                       text=True)
    assert r.returncode == 2
    r = subprocess.run([sys.executable, cli, "check-trace",
                        str(tmp_path / "missing.json")], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 2


def test_fedrace_cli_smoke(tmp_path):
    """FEDML_RACE_QUICK smoke (ISSUE 17): the fedrace CLI contract —
    `check --json` exits 0 with zero unsuppressed findings and the
    extracted scopes attached, an `--update-manifest` round-trip to a
    fresh path reproduces the committed pin's measured half, a tampered
    manifest exits 1 naming manifest-drift, and bad usage exits 2.  Pure
    stdlib (no jax import in the CLI)."""
    import subprocess

    cli = os.path.join(REPO, "tools", "fedrace.py")

    r = subprocess.run([sys.executable, cli, "check", "--json"], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert [f for f in payload["findings"] if not f["suppressed"]] == []
    committed = json.load(open(os.path.join(
        REPO, "tests", "data", "fedrace", "concurrency.json")))
    assert set(payload["scopes"]) == set(committed["scopes"])

    # --update-manifest round-trip: fresh pin == committed measured half
    fresh = str(tmp_path / "concurrency.json")
    r = subprocess.run([sys.executable, cli, "check", "--manifest", fresh,
                        "--update-manifest"], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.load(open(fresh))
    assert got["scopes"] == committed["scopes"]
    assert got["lock_order"] == committed["lock_order"]

    # tampered pin = reviewed-diff failure (exit 1, manifest-drift named)
    del got["scopes"]["staging.AsyncCohortStager"]["locks"]["_lock"]
    with open(fresh, "w") as fh:
        json.dump(got, fh)
    r = subprocess.run([sys.executable, cli, "check", "--manifest", fresh],
                       cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 1 and "manifest-drift" in r.stdout

    # usage errors exit 2; --list-rules documents every rule family
    r = subprocess.run([sys.executable, cli], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 2
    r = subprocess.run([sys.executable, cli, "--list-rules"], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0
    for rule in ("unguarded-shared-write", "lock-order-cycle",
                 "blocking-under-lock", "leaked-thread"):
        assert rule in r.stdout
