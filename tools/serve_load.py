#!/usr/bin/env python
"""serve_load — closed-loop load harness for the multi-tenant serving
engine (ISSUE 9 / ROADMAP open item 4: the serving plane had never been
load-tested).

Drives a :class:`~fedml_tpu.serving.batching.ContinuousBatchingEngine` at
a **target RPS** with the traffic shape production LoRA serving actually
sees:

- **Poisson arrivals** at ``--rps`` (exponential inter-arrival gaps) —
  open-loop admission, so a saturated engine shows up as admission-queue
  depth and latency growth rather than a silently throttled driver;
- **heavy-tailed prompt lengths** (log-normal, clipped to the engine's
  buffer) — the short-request-behind-long-request case continuous
  batching exists for;
- **Zipf adapter popularity** over the registered adapters plus base
  traffic — a few hot cohorts, a long cold tail, every request landing
  on the ONE shared batched program.

Each request's **latency** is measured from its scheduled arrival to its
completion (so scheduler lag and queueing both count, like a client would
experience), **TTFT** to its first emitted token.  The report carries
p50/p99 of both, aggregate tokens/s, achieved admission RPS vs target,
and the admission-queue depth envelope — the numbers ``bench.py
--serve-mt`` folds into the BENCH json.

``--multi N`` (fedslo, docs/OBSERVABILITY.md) drives N independent
engine replicas, scrapes each one's live ``/metrics``, and merges the
native ``serve_ttft_seconds`` histograms by bucket addition into FLEET
percentiles — then cross-checks the bucket-estimated fleet p50/p99
against the harness's exact sample percentiles (must agree within one
bucket width, the merge-correctness canary for multi-replica scrapes).

Usage (self-contained tiny-model demo):
    python tools/serve_load.py [--rps 20] [--requests 64] [--adapters 8]
Writes SERVE_LOAD.json at the repo root; ``run_load`` / ``run_fleet``
are importable for driving any engine(s) in-process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the traffic shapes are shared with the async arrival simulator
# (fedml_tpu/core/traffic.py, docs/ASYNC.md); zipf_weights stays re-exported
# here so `from serve_load import zipf_weights` keeps working
from fedml_tpu.core.traffic import (  # noqa: E402
    lognormal_sizes, poisson_arrivals, zipf_weights)


def _percentile(vals: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q)) \
        if len(vals) else 0.0


def run_load(engine, *, target_rps: float, n_requests: int,
             adapters: Sequence[Optional[str]] = (None,),
             zipf_a: float = 1.2, prompt_len_mean: float = 8.0,
             prompt_len_sigma: float = 0.8, max_new_tokens: int = 16,
             vocab: int = 256, seed: int = 0,
             timeout_s: float = 300.0,
             scrape_url: Optional[str] = None,
             scrape_rel_tol: float = 0.6,
             keep_samples: bool = False) -> Dict:
    """Drive ``engine`` at ``target_rps`` and report the latency/throughput
    envelope.  ``adapters`` lists the routing choices in popularity order
    (``None`` = base traffic); the Zipf mix makes the first entries hot.

    ``scrape_url`` (fedmon, docs/OBSERVABILITY.md): a live ``/metrics``
    endpoint scraped MID-RUN (at ~60% of submissions, off the submit
    thread).  The report then cross-checks the engine's own gauges
    against this harness's independent measurements — ``serve.tokens_
    total`` must sit inside the run's token envelope, ``serve.tokens_
    per_s`` within ``scrape_rel_tol`` of the measured aggregate, and the
    queue-depth gauge inside the observed envelope — the silent-counter-
    drift canary (``report["scrape"]["ok"]``).

    The caller should warm the engine's compiled programs first (one
    request per distinct program) — this harness measures serving, not
    XLA compilation.
    """
    rng = np.random.default_rng(seed)
    arrival = poisson_arrivals(rng, target_rps, n_requests)
    weights = zipf_weights(len(adapters), zipf_a)
    choice = rng.choice(len(adapters), size=n_requests, p=weights)
    lens = lognormal_sizes(rng, prompt_len_mean, prompt_len_sigma,
                           n_requests,
                           hi=max(1, engine.buf_len - max_new_tokens - 1))
    prompts = [rng.integers(2, vocab, int(n)).tolist() for n in lens]

    lat: List[float] = [0.0] * n_requests
    ttft: List[float] = [0.0] * n_requests
    # first token since the actual submit call (the engine's own ttft
    # clock convention) — what histogram cross-checks compare against
    ttft_sub: List[float] = [0.0] * n_requests
    toks: List[int] = [0] * n_requests
    failed: List[int] = []
    queue_depths: List[int] = []
    lock = threading.Lock()
    # harness-side token clock (independent of the engine's counters):
    # every received token bumps it, so the scrape can measure ITS OWN
    # windowed tokens/s to compare against the engine's windowed gauge
    tok_clock = [0]

    def collect(i: int, q, t_sched: float, t_sub: float):
        first = None
        count = 0
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                t = q.get(timeout=max(deadline - time.monotonic(), 0.001))
            except Exception:  # queue.Empty — engine wedged
                with lock:
                    failed.append(i)
                return
            now = time.monotonic()
            if first is None:
                first = now
            if t is None:
                break
            count += 1
            with lock:
                tok_clock[0] += 1
        with lock:
            lat[i] = now - t_sched
            ttft[i] = first - t_sched
            ttft_sub[i] = first - t_sub
            toks[i] = count

    scrape: Dict[str, float] = {}

    def do_scrape():
        import urllib.request
        from fedml_tpu.obs.metricsd import (parse_prometheus_text,
                                            prom_value)
        url = scrape_url.rstrip("/")
        if not url.endswith("/metrics"):
            url += "/metrics"
        try:
            # harness-side windowed rate over the ~same window the
            # engine's serve.tokens_per_s gauge integrates (0.5s+),
            # measured from the independent token clock
            with lock:
                n0 = tok_clock[0]
            w0 = time.monotonic()
            time.sleep(0.8)
            with lock:
                n1 = tok_clock[0]
            w1 = time.monotonic()
            scrape["_harness_tokens_per_s"] = (n1 - n0) / max(w1 - w0,
                                                              1e-9)
            text = urllib.request.urlopen(url, timeout=10).read().decode()
            samples = parse_prometheus_text(text)
            for gauge in ("serve.tokens_per_s", "serve.tokens_total",
                          "serve.queue_depth"):
                v = prom_value(samples, "fedtrace_counter", name=gauge)
                if v is not None:
                    scrape[gauge] = v
            scrape["_t"] = time.monotonic()
        except Exception as e:   # a failed scrape is a result, not a crash
            scrape["_error"] = repr(e)  # type: ignore[assignment]

    threads = []
    t0 = time.monotonic()
    adapter_counts: Dict[str, int] = {}
    behind_s = 0.0
    scrape_at = max(1, int(0.6 * n_requests))
    scrape_thread = None
    for i in range(n_requests):
        t_sched = t0 + arrival[i]
        now = time.monotonic()
        if now < t_sched:
            time.sleep(t_sched - now)
        else:
            behind_s = max(behind_s, now - t_sched)
        name = adapters[int(choice[i])]
        adapter_counts[name or "base"] = \
            adapter_counts.get(name or "base", 0) + 1
        t_sub = time.monotonic()
        q = engine.submit(prompts[i], max_new_tokens=max_new_tokens,
                          adapter=name) if name is not None else \
            engine.submit(prompts[i], max_new_tokens=max_new_tokens)
        queue_depths.append(engine._waiting.qsize())
        if scrape_url and i == scrape_at:
            scrape_thread = threading.Thread(target=do_scrape, daemon=True)
            scrape_thread.start()
        th = threading.Thread(target=collect, args=(i, q, t_sched, t_sub),
                              daemon=True)
        th.start()
        threads.append(th)
    t_last_submit = time.monotonic()
    for th in threads:
        th.join(timeout=timeout_s)
    t_end = time.monotonic()
    if scrape_thread is not None:
        scrape_thread.join(timeout=30.0)

    ok = [i for i in range(n_requests) if i not in set(failed)]
    lat_ok = [lat[i] for i in ok]
    ttft_ok = [ttft[i] for i in ok]
    total_toks = sum(toks[i] for i in ok)
    makespan = max(t_end - t0, 1e-9)
    scrape_report = None
    if scrape_url:
        scrape_report = {"url": scrape_url}
        if "_error" in scrape:
            scrape_report.update(ok=False, error=scrape["_error"])
        elif not scrape:
            scrape_report.update(ok=False, error="scrape never ran "
                                 "(fewer submissions than scrape point?)")
        else:
            measured_tps = total_toks / makespan
            harness_tps = scrape.get("_harness_tokens_per_s", 0.0)
            gauge_tps = scrape.get("serve.tokens_per_s")
            gauge_total = scrape.get("serve.tokens_total")
            gauge_depth = scrape.get("serve.queue_depth")
            # like-for-like rate comparison: the engine gauge is a short
            # windowed rate, so compare it against the harness's OWN
            # windowed rate at scrape time; the bound allows rel_tol of
            # the larger rate plus a small absolute floor (window phase
            # offset between the two clocks)
            tps_bound = (scrape_rel_tol * max(harness_tps, gauge_tps or 0.0)
                         + 0.1 * max(measured_tps, 1.0))
            checks = {
                # mid-run cumulative total must sit inside [0, final]
                "tokens_total_in_envelope": (
                    gauge_total is None
                    or 0.0 <= gauge_total <= total_toks),
                "tokens_per_s_agree": (
                    gauge_tps is None or harness_tps <= 0
                    or abs(gauge_tps - harness_tps) <= tps_bound),
                # the gauge can never exceed the worst depth we saw
                "queue_depth_in_envelope": (
                    gauge_depth is None
                    or gauge_depth <= max(queue_depths, default=0) + 1),
            }
            scrape_report.update(
                ok=all(checks.values()), checks=checks,
                tokens_per_s_gauge=gauge_tps,
                tokens_per_s_harness_window=round(harness_tps, 1),
                tokens_per_s_measured=round(measured_tps, 1),
                tokens_total_gauge=gauge_total,
                queue_depth_gauge=gauge_depth,
                rel_tol=scrape_rel_tol)
    return {
        "target_rps": float(target_rps),
        "requests": n_requests,
        "completed": len(ok),
        "failed": len(failed),
        "achieved_admission_rps": round(
            n_requests / max(t_last_submit - t0, 1e-9), 2),
        "driver_max_lag_s": round(behind_s, 4),
        "latency_p50_ms": round(_percentile(lat_ok, 50) * 1e3, 2),
        "latency_p99_ms": round(_percentile(lat_ok, 99) * 1e3, 2),
        "ttft_p50_ms": round(_percentile(ttft_ok, 50) * 1e3, 2),
        "ttft_p99_ms": round(_percentile(ttft_ok, 99) * 1e3, 2),
        "tokens_total": int(total_toks),
        "tokens_per_s": round(total_toks / makespan, 1),
        "queue_depth_max": int(max(queue_depths, default=0)),
        "queue_depth_mean": round(float(np.mean(queue_depths))
                                  if queue_depths else 0.0, 2),
        "adapter_request_counts": adapter_counts,
        "prompt_len_mean_actual": round(float(np.mean(lens)), 1),
        "prompt_len_max_actual": int(np.max(lens)),
        "makespan_s": round(makespan, 3),
        **({"scrape": scrape_report} if scrape_report is not None else {}),
        # raw per-request samples for fleet-level exact percentiles
        # (run_fleet pops this before reporting)
        **({"_samples": {"ttft": ttft_ok,
                         "ttft_submit": [ttft_sub[i] for i in ok],
                         "latency": lat_ok}}
           if keep_samples else {}),
    }


def merge_fleet_histograms(texts: Sequence[str],
                           metric: str = "serve_ttft_seconds",
                           label_key: str = "adapter",
                           baseline_texts: Optional[Sequence[str]] = None
                           ) -> Dict:
    """Merge N engines' ``/metrics`` texts into fleet histogram entries
    (fedslo, docs/OBSERVABILITY.md): parse each scrape, reassemble the
    native histogram per adapter label, and add buckets — valid because
    every engine shares the same fixed boundary grid.

    ``baseline_texts`` (one earlier scrape per engine, same order)
    subtracts each engine's pre-window counts first — the Prometheus
    ``rate()`` discipline, which is how warm-up/compile requests are
    kept out of a measurement window over cumulative histograms.

    Returns ``{"labels": {label: entry}, "fleet": entry|None}`` where
    each entry is ``snapshot()``-shaped (feed it straight to
    :func:`~fedml_tpu.obs.histogram.quantile_from_buckets`).
    """
    from fedml_tpu.obs.histogram import (buckets_from_samples,
                                         diff_bucket_entries,
                                         merge_bucket_entries)
    from fedml_tpu.obs.metricsd import parse_prometheus_text
    per_engine = [buckets_from_samples(parse_prometheus_text(t), metric,
                                       label_key=label_key)
                  for t in texts]
    if baseline_texts is not None:
        base = [buckets_from_samples(parse_prometheus_text(t), metric,
                                     label_key=label_key)
                for t in baseline_texts]
        per_engine = [{lbl: diff_bucket_entries(e, b.get(lbl))
                       for lbl, e in pe.items()}
                      for pe, b in zip(per_engine, base)]
    labels = sorted({lbl for pe in per_engine for lbl in pe})
    merged = {lbl: merge_bucket_entries([pe.get(lbl) for pe in per_engine])
              for lbl in labels}
    fleet = merge_bucket_entries([e for pe in per_engine
                                  for e in pe.values()])
    return {"labels": merged, "fleet": fleet}


def run_fleet(engines: Sequence, metrics_urls: Sequence[str], *,
              target_rps: float, n_requests: int,
              adapters: Sequence[Optional[str]] = (None,),
              max_new_tokens: int = 16, vocab: int = 256, seed: int = 0,
              timeout_s: float = 300.0) -> Dict:
    """Drive each engine replica with an equal share of the load, scrape
    every live ``/metrics`` endpoint, and merge the per-engine native
    TTFT histograms into fleet percentiles by bucket addition.

    The cross-check: the bucket-estimated fleet p50/p99 must land within
    one bucket width of the harness's exact sample percentiles over ALL
    replicas' requests (``merge_ok``) — if merging were wrong (boundary
    drift, double count, dropped replica) the estimate falls outside the
    width guarantee of a single correct histogram.
    """
    import urllib.request

    from fedml_tpu.obs.histogram import (bucket_width_at,
                                         quantile_from_buckets)

    def _scrape(u: str) -> str:
        url = u.rstrip("/")
        if not url.endswith("/metrics"):
            url += "/metrics"
        return urllib.request.urlopen(url, timeout=10).read().decode()

    n_eng = len(engines)
    share = max(1, n_requests // n_eng)
    # pre-window scrape: whatever the engines served before this run
    # (warm-up/compile requests) is subtracted rate()-style
    baseline_texts = [_scrape(u) for u in metrics_urls]
    reports: List[Dict] = []
    ttft_all: List[float] = []
    for k, eng in enumerate(engines):
        rep = run_load(eng, target_rps=target_rps / n_eng,
                       n_requests=share, adapters=adapters,
                       max_new_tokens=max_new_tokens, vocab=vocab,
                       seed=seed + 101 * k, timeout_s=timeout_s,
                       keep_samples=True)
        # submit-based samples: the engine's own ttft clock convention,
        # so the check exercises the histogram algebra, not the gap
        # between scheduled-arrival and submit clocks
        ttft_all.extend(rep.pop("_samples")["ttft_submit"])
        reports.append(rep)
    texts = [_scrape(u) for u in metrics_urls]
    merged = merge_fleet_histograms(texts, metric="serve_ttft_seconds",
                                    baseline_texts=baseline_texts)
    fleet = merged["fleet"]
    checks: Dict[str, bool] = {}
    fleet_pct: Dict[str, Optional[float]] = {}
    if fleet is not None and ttft_all:
        for qname, q in (("p50", 0.50), ("p99", 0.99)):
            est = quantile_from_buckets(fleet, q)
            exact = _percentile(ttft_all, q * 100.0)
            width = bucket_width_at(fleet, exact)
            fleet_pct[qname] = est
            checks[f"ttft_{qname}_within_bucket"] = (
                est is not None and abs(est - exact) <= width + 1e-9)
        checks["fleet_count_matches"] = \
            fleet["count"] == sum(r["completed"] for r in reports)
    return {
        "engines": n_eng,
        "fleet_requests": sum(r["completed"] for r in reports),
        "fleet_failed": sum(r["failed"] for r in reports),
        "fleet_tokens_per_s": round(sum(r["tokens_per_s"]
                                        for r in reports), 1),
        "fleet_ttft_p50_ms": round((fleet_pct.get("p50") or 0.0) * 1e3, 2),
        "fleet_ttft_p99_ms": round((fleet_pct.get("p99") or 0.0) * 1e3, 2),
        "fleet_hist_count": int(fleet["count"]) if fleet else 0,
        "adapter_labels": sorted(merged["labels"]),
        "merge_checks": checks,
        "merge_ok": bool(checks) and all(checks.values()),
        "per_engine": reports,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rps", type=float, default=20.0)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--adapters", type=int, default=8,
                    help="registered LoRA adapters (plus base traffic)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(REPO, "SERVE_LOAD.json"))
    ap.add_argument("--multi", type=int, default=1, metavar="N",
                    help="drive N engine replicas, scrape each /metrics, "
                         "merge the native TTFT histograms into fleet "
                         "percentiles and cross-check them against exact "
                         "sample percentiles (fedslo)")
    ap.add_argument("--scrape-metrics", default=None, metavar="URL",
                    help="scrape this live fedmon /metrics endpoint "
                         "mid-run and cross-check the serve.* gauges "
                         "against the harness's own measurements "
                         "('self' starts an in-process endpoint over "
                         "the engine's tracer)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import fedml_tpu  # noqa: F401 (backend pin)
    from fedml_tpu.llm.fedllm import lora_init
    from fedml_tpu.llm.model import LlamaConfig, LlamaLM
    from fedml_tpu.serving.batching import ContinuousBatchingEngine

    buf_len = 128
    cfg = LlamaConfig(vocab_size=258, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=4, ffn_dim=128, max_seq_len=buf_len,
                      dtype=jnp.float32, lora_rank=8)
    model = LlamaLM(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))

    if args.multi > 1:
        engines = []
        for _k in range(args.multi):
            eng = ContinuousBatchingEngine(
                model, variables["params"], slots=args.slots,
                buf_len=buf_len, adapter_slots=args.adapters + 2,
                metrics_port=0)
            for i in range(args.adapters):
                eng.registry.register(
                    f"cohort{i}",
                    lora_init(jax.random.PRNGKey(100 + i),
                              variables["lora"]))
            engines.append(eng)
        names = [f"cohort{i}" for i in range(args.adapters)]
        try:
            for eng in engines:   # warm both compiled programs off-clock
                eng.generate([5, 17, 42], max_new_tokens=2,
                             adapter=names[0] if names else None)
            report = run_fleet(
                engines, [e.metrics_server.url for e in engines],
                target_rps=args.rps, n_requests=args.requests,
                adapters=[None] + names,
                max_new_tokens=args.max_new_tokens,
                vocab=cfg.vocab_size, seed=args.seed)
        finally:
            for eng in engines:
                eng.stop()
        print(json.dumps(report))
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        return

    engine = ContinuousBatchingEngine(
        model, variables["params"], slots=args.slots, buf_len=buf_len,
        adapter_slots=args.adapters + 2)
    names = []
    for i in range(args.adapters):
        name = f"cohort{i}"
        engine.registry.register(
            name, lora_init(jax.random.PRNGKey(100 + i), variables["lora"]))
        names.append(name)
    scrape_url = args.scrape_metrics
    metrics_server = None
    if scrape_url == "self":
        # the serve.* gauges only exist with the tracer on; an ephemeral
        # endpoint over the global tracer is the self-contained demo
        from fedml_tpu import obs
        obs.configure(enabled=True, reset=True)
        from fedml_tpu.obs.metricsd import MetricsServer
        metrics_server = MetricsServer()
        metrics_server.start()
        scrape_url = metrics_server.url
    try:
        # warm both compiled programs (prefill + batched step) off-clock
        engine.generate([5, 17, 42], max_new_tokens=2, adapter=names[0])
        report = run_load(
            engine, target_rps=args.rps, n_requests=args.requests,
            adapters=[None] + names, max_new_tokens=args.max_new_tokens,
            vocab=cfg.vocab_size, seed=args.seed, scrape_url=scrape_url)
    finally:
        engine.stop()
        if metrics_server is not None:
            metrics_server.close()
    report["engine"] = {"slots": args.slots, "buf_len": buf_len,
                        "adapters_registered": len(names)}
    print(json.dumps(report))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
