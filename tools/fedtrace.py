#!/usr/bin/env python
"""fedtrace — analyze fedml_tpu Chrome trace-event captures.

Pure stdlib (runs without jax installed, like ``tools/fedlint.py``):

- ``fedtrace.py summarize TRACE.json [--json]`` — span totals, counters,
  and the per-phase (staging / gather / client_steps / merge /
  server_update) round-time breakdown.
- ``fedtrace.py diff A.json B.json [--json]`` — per-phase comparison of
  two traces (e.g. fused vs. unfused, or two commits).
- ``fedtrace.py merge --out M.json A.json B.json ...`` — align N
  per-process captures of one federation run on a handshake-estimated
  clock offset into ONE Perfetto-loadable timeline (fedscope).
- ``fedtrace.py critical-path MERGED.json [--round R]`` — walk each
  round's span DAG (cross-process edges via the propagated span ids)
  and report the gating chain + per-silo straggler ranking.
- ``fedtrace.py regress CURRENT.json [--bands F] [--baseline-dir D]`` —
  per-metric tolerance gate of a bench row against the committed
  ``BENCH_r*.json`` trajectory; exit 3 on regression.
- ``fedtrace.py health TRACE.json [--json]`` — offline federation-health
  report from a captured trace (fedmon, docs/OBSERVABILITY.md): the
  per-round ``health.*`` counter trajectory, every flagged client with
  its score/reason, and the drift envelope.

Attribution model (docs/OBSERVABILITY.md): ``staging`` is measured
directly from host spans; the four device phases are apportioned from
each round's measured wall-clock (the ``obs.round`` counter's
``round_time_s``) proportionally to the per-phase FLOP weights the
compiled round records on device (``ObsCarry.phase_flops``) — unless the
trace carries MEASURED per-phase device durations (the ``device.<p>_s``
counters the ``trace_device`` probe emits), which replace the FLOP proxy.

Exit codes: 0 ok, 1 malformed trace / bad input, 2 usage error,
3 regression detected (``regress`` only).
"""

from __future__ import annotations

import argparse
import fnmatch
import glob as glob_mod
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PHASES = ("gather", "client_steps", "merge", "server_update")
PHASES = ("staging",) + DEVICE_PHASES

#: counter names of the measured device-phase probe (obs/devicetime.py)
MEASURED_PHASE_COUNTERS = {p: f"device.{p}_s" for p in DEVICE_PHASES}


def load_trace(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        trace = json.load(fh)
    if isinstance(trace, list):  # bare-array Chrome format
        trace = {"traceEvents": trace}
    if "traceEvents" not in trace:
        raise ValueError(f"{path}: no traceEvents key")
    return trace


def validate_events(events: List[dict]) -> List[str]:
    """Schema check: required keys, monotonic ts, paired B/E per thread.
    Returns a list of problems (empty == valid)."""
    problems: List[str] = []
    last_ts = None
    stacks: Dict[Any, List[str]] = {}
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if "name" not in ev or ph is None:
            problems.append(f"event {i}: missing name/ph")
            continue
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i} ({ev['name']}): missing ts")
            continue
        if last_ts is not None and ts < last_ts:
            problems.append(f"event {i} ({ev['name']}): ts not monotonic "
                            f"({ts} < {last_ts})")
        last_ts = ts
        tid = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            stacks.setdefault(tid, []).append(ev["name"])
        elif ph == "E":
            stack = stacks.get(tid, [])
            if ev["name"] in stack:
                # pop through (tolerates interleaved-but-paired spans)
                while stack and stack[-1] != ev["name"]:
                    stack.pop()
                if stack:
                    stack.pop()
            else:
                problems.append(f"event {i}: E '{ev['name']}' without B "
                                f"on tid {tid}")
        elif ph not in ("C", "i", "X"):
            problems.append(f"event {i}: unknown ph {ph!r}")
    for tid, stack in stacks.items():
        for name in stack:
            problems.append(f"unclosed B '{name}' on tid {tid}")
    return problems


def span_totals(events: List[dict]) -> Dict[str, Dict[str, float]]:
    """Per-name span aggregation from paired B/E events."""
    open_: Dict[Any, List[tuple]] = {}
    agg: Dict[str, Dict[str, float]] = {}
    for ev in events:
        ph = ev.get("ph")
        tid = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            open_.setdefault(tid, []).append((ev["name"], ev["ts"]))
        elif ph == "E":
            stack = open_.get(tid, [])
            for i in range(len(stack) - 1, -1, -1):
                if stack[i][0] == ev["name"]:
                    name, t0 = stack.pop(i)
                    row = agg.setdefault(name, {"count": 0, "total_s": 0.0})
                    row["count"] += 1
                    row["total_s"] += (ev["ts"] - t0) / 1e6
                    break
    return agg


def counter_last(events: List[dict]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for ev in events:
        if ev.get("ph") == "C" and ev.get("name") != "obs.round":
            v = (ev.get("args") or {}).get("value")
            if isinstance(v, (int, float)):
                out[ev["name"]] = float(v)
    return out


def round_records(events: List[dict]) -> List[dict]:
    return [dict(ev.get("args") or {}) for ev in events
            if ev.get("ph") == "C" and ev.get("name") == "obs.round"]


def measured_phase_seconds(events: List[dict]) -> Optional[Dict[str, float]]:
    """Measured per-phase device durations from the ``device.<p>_s``
    counters (the ``trace_device`` probe, obs/devicetime.py) — present
    only when the run opted into the out-of-band measurement.  Requires
    ALL four phases so the attribution never mixes measured and modeled
    weights."""
    counters = counter_last(events)
    out = {}
    for p, name in MEASURED_PHASE_COUNTERS.items():
        v = counters.get(name)
        if not isinstance(v, float) or v <= 0:
            return None
        out[p] = v
    return out


def phase_breakdown(events: List[dict],
                    spans: Optional[Dict[str, Dict[str, float]]] = None
                    ) -> Dict[str, Any]:
    """Per-phase seconds: staging measured from spans; device phases
    attributed from per-round wall-clock × on-device FLOP weights — or,
    when the trace carries the measured device-phase counters, × the
    MEASURED per-phase durations (proxy kept as fallback)."""
    spans = spans if spans is not None else span_totals(events)
    rounds = round_records(events)
    measured = measured_phase_seconds(events)
    phases = {p: 0.0 for p in PHASES}
    phases["staging"] = spans.get("staging", {}).get("total_s", 0.0)
    total_round_s = 0.0
    for rec in rounds:
        rt = float(rec.get("round_time_s", 0.0))
        total_round_s += rt
        if measured is not None:
            weights = [measured[p] for p in DEVICE_PHASES]
        else:
            weights = [max(float(rec.get(f"flops_{p}", 0.0)), 0.0)
                       for p in DEVICE_PHASES]
        wsum = sum(weights)
        if wsum <= 0:
            continue
        for p, w in zip(DEVICE_PHASES, weights):
            phases[p] += rt * (w / wsum)
    out = {
        "phases": {p: round(v, 6) for p, v in phases.items()},
        "rounds": len(rounds),
        "round_time_total_s": round(total_round_s, 6),
        "compile_s": round(spans.get("xla_compile", {}).get("total_s", 0.0),
                           6),
        "compile_count": int(spans.get("xla_compile", {}).get("count", 0)),
    }
    if measured is not None:
        out["device_phase_source"] = "measured"
        out["device_phases_measured_s"] = {p: round(v, 6)
                                           for p, v in measured.items()}
        # measured-vs-modeled share deltas: how far the FLOP proxy was off
        # (bench.py --trace archives these into the BENCH json)
        modeled = {p: 0.0 for p in DEVICE_PHASES}
        for rec in rounds:
            w = [max(float(rec.get(f"flops_{p}", 0.0)), 0.0)
                 for p in DEVICE_PHASES]
            ws = sum(w)
            if ws <= 0:
                continue
            for p, v in zip(DEVICE_PHASES, w):
                modeled[p] += v / ws
        n = max(len(rounds), 1)
        msum = sum(measured.values())
        out["device_phase_delta"] = {
            p: round(measured[p] / msum - modeled[p] / n, 6)
            for p in DEVICE_PHASES}
    return out


def summarize(trace: Dict[str, Any]) -> Dict[str, Any]:
    events = trace["traceEvents"]
    spans = span_totals(events)
    out = phase_breakdown(events, spans)
    out["spans"] = {n: {"count": int(r["count"]),
                        "total_s": round(r["total_s"], 6)}
                    for n, r in sorted(spans.items())}
    out["counters"] = counter_last(events)
    recs = round_records(events)
    if recs:
        out["update_norm_last"] = round(
            float(recs[-1].get("update_norm", 0.0)), 6)
        out["examples_total"] = round(
            sum(float(r.get("examples", 0.0)) for r in recs), 1)
        # low-precision collective layer (docs/COLLECTIVE_PRECISION.md):
        # modeled interconnect payload of the merge+broadcast collectives
        # and the quantization-residual norm the round carried on device
        cb = [float(r["collective_bytes"]) for r in recs
              if "collective_bytes" in r]
        if cb:
            out["collective_bytes_per_round"] = round(sum(cb) / len(cb), 1)
            out["collective_bytes_total"] = round(sum(cb), 1)
        # per-mesh-axis split (docs/MESH_2D.md, docs/PIPELINE.md):
        # merge/broadcast payload on ``client``, the pipeline permute +
        # flat-view traffic on ``stage`` (3-D layouts only), model-parallel
        # traffic on ``model``
        for axis in ("client", "stage", "model"):
            vals = [float(r[f"collective_bytes_{axis}"]) for r in recs
                    if f"collective_bytes_{axis}" in r]
            if vals:
                out[f"collective_bytes_{axis}_per_round"] = round(
                    sum(vals) / len(vals), 1)
        qe = [float(r["quant_error_norm"]) for r in recs
              if "quant_error_norm" in r]
        if qe:
            out["quant_error_norm_last"] = round(qe[-1], 6)
        # vmapped experiment population (docs/PRIMITIVES.md): per-member
        # loss envelope of the (P,)-stacked ObsCarry, plus the pinned
        # bytes-identical-across-members invariant (a nonzero spread means
        # members traced different programs)
        mem = [r for r in recs if "members" in r]
        if mem:
            out["population_members"] = int(float(mem[-1]["members"]))
            out["member_loss_best_last"] = round(
                float(mem[-1]["member_loss_best"]), 6)
            out["member_loss_worst_last"] = round(
                float(mem[-1]["member_loss_worst"]), 6)
            out["member_bytes_spread_max"] = round(
                max(float(r.get("member_bytes_spread", 0.0)) for r in mem),
                6)
    # paged client-state store (fedstore, docs/CLIENT_STORE.md): the
    # host-plane paging counters the store/pager emit — cumulative bytes
    # paged in, the final prefetch hit rate, and the write-back lag
    # (write-backs still pending when the last gather ran)
    counters = out["counters"]
    if "store.page_in_bytes" in counters:
        out["page_in_bytes"] = counters["store.page_in_bytes"]
    if "store.page_hit_rate" in counters:
        out["page_hit_rate"] = round(counters["store.page_hit_rate"], 6)
    if "store.writeback_lag_rounds" in counters:
        out["writeback_lag_rounds"] = counters["store.writeback_lag_rounds"]
    # buffered-async plane (fedbuff, docs/ASYNC.md): last-apply buffer
    # occupancy, the per-apply staleness envelope, and the cumulative
    # dropped-update count the engine emits at every buffer apply
    if "async.buffer_occupancy" in counters:
        out["buffer_occupancy_last"] = counters["async.buffer_occupancy"]
    if "async.staleness_p50" in counters:
        out["staleness_p50"] = round(counters["async.staleness_p50"], 6)
    if "async.staleness_p99" in counters:
        out["staleness_p99"] = round(counters["async.staleness_p99"], 6)
    if "async.updates_dropped" in counters:
        out["async_updates_dropped"] = counters["async.updates_dropped"]
    if "async.sim_time_s" in counters:
        out["async_sim_time_s"] = round(counters["async.sim_time_s"], 6)
    # fedguard fault-tolerance plane (docs/FAULT_TOLERANCE.md): retry
    # totals of the reliable-delivery layer, the per-round quorum
    # trajectory (every comm.quorum_size sample, in order — the shape of
    # a chaos run: full, then degraded, then healed), and the lease-dead
    # rank gauge
    if "comm.retries" in counters:
        out["comm_retries_total"] = counters["comm.retries"]
    if "comm.retry_rate" in counters:
        out["comm_retry_rate_last"] = round(counters["comm.retry_rate"], 6)
    if "comm.retry_exhausted" in counters:
        out["comm_retry_exhausted"] = counters["comm.retry_exhausted"]
    if "comm.ack_rtt" in counters:
        out["comm_ack_rtt_last_s"] = round(counters["comm.ack_rtt"], 6)
    quorum_traj = [int(e["args"]["value"]) for e in events
                   if e.get("ph") == "C"
                   and e.get("name") == "comm.quorum_size"]
    if quorum_traj:
        out["quorum_trajectory"] = quorum_traj
        out["quorum_size_last"] = quorum_traj[-1]
        out["quorum_size_min"] = min(quorum_traj)
    if "comm.dead_ranks" in counters:
        out["dead_ranks_last"] = counters["comm.dead_ranks"]
    if "comm.dup_dropped" in counters:
        out["comm_dup_dropped"] = counters["comm.dup_dropped"]
    # fedwire quantized wire plane (docs/WIRE.md): cumulative encoded
    # payload bytes, the codec's byte-model prediction, the last EF
    # residual norm, chunk-frame totals — and the headline
    # ``wire_bytes_ratio``: measured silo<->server wire bytes over the
    # modeled census.  ~1.0x (framing overhead only) proves the census
    # math IS what the wire carries; a tolerance band pins it in tests.
    if "wire.bytes" in counters:
        out["wire_bytes_total"] = counters["wire.bytes"]
    if "wire.modeled_bytes" in counters:
        out["wire_modeled_bytes_total"] = counters["wire.modeled_bytes"]
        measured = counters.get("comm.bytes.silo_server")
        if measured:
            out["wire_bytes_ratio"] = round(
                float(measured) / float(counters["wire.modeled_bytes"]), 6)
    if "wire.ef_norm" in counters:
        out["wire_ef_norm_last"] = round(counters["wire.ef_norm"], 6)
    if "comm.chunks_sent" in counters:
        out["comm_chunks_sent"] = counters["comm.chunks_sent"]
    # multi-tenant serving plane (docs/SERVING.md): admission spans and
    # the batching engine's host counters — admission-queue depth,
    # windowed tokens/s, and per-adapter request counts ("base" is
    # adapterless traffic on the zero bank row)
    if "serve.admit" in out["spans"]:
        out["serve_admits"] = out["spans"]["serve.admit"]["count"]
    if "serve.queue_depth" in counters:
        out["serve_queue_depth_last"] = counters["serve.queue_depth"]
    if "serve.tokens_per_s" in counters:
        out["serve_tokens_per_s_last"] = round(
            counters["serve.tokens_per_s"], 6)
    if "serve.tokens_total" in counters:
        out["serve_tokens_total"] = counters["serve.tokens_total"]
    # paged-KV memory plane (docs/SERVING.md): pool headroom at trace
    # end, the cumulative prefix page-share rate, chunked-prefill volume,
    # and the adapter HBM-cache hit/miss/eviction counters of store-mode
    # engines — the knobs' feedback loop (resize kv_pool_pages /
    # adapter_cache_slots on these)
    if "serve.kv_pages_free" in counters:
        out["serve_kv_pages_free_last"] = counters["serve.kv_pages_free"]
    if "serve.kv_page_hit_rate" in counters:
        out["serve_kv_page_hit_rate"] = round(
            counters["serve.kv_page_hit_rate"], 6)
    if "serve.prefill_chunks" in counters:
        out["serve_prefill_chunks"] = counters["serve.prefill_chunks"]
    if "serve.adapter_cache_hits" in counters:
        out["serve_adapter_cache"] = {
            "hits": counters["serve.adapter_cache_hits"],
            "misses": counters.get("serve.adapter_cache_misses", 0),
            "evictions": counters.get("serve.adapter_cache_evictions", 0),
        }
    if "serve.adapter_miss_rate" in counters:
        out["serve_adapter_miss_rate_last"] = round(
            counters["serve.adapter_miss_rate"], 6)
    # per-adapter request counts: the bounded-label counter (ONE metric,
    # ``adapter`` arg, capped at top-K + "other") is authoritative; the
    # deprecated per-adapter metric NAMES (serve.requests.<name>, behind
    # FEDML_SERVE_LEGACY_ADAPTER_COUNTERS for one release) merge in by
    # max so a flag-on trace doesn't double count
    adapter_reqs: Dict[str, int] = {}
    for e in events:
        if (e.get("ph") == "C"
                and e.get("name") == "serve.requests_by_adapter"):
            a = e.get("args") or {}
            if "adapter" in a:
                adapter_reqs[str(a["adapter"])] = int(a["value"])
    for k, v in counters.items():
        if k.startswith("serve.requests."):
            name = k[len("serve.requests."):]
            adapter_reqs[name] = max(adapter_reqs.get(name, 0), int(v))
    if adapter_reqs:
        out["serve_adapter_requests"] = adapter_reqs
        total_req = sum(adapter_reqs.values())
        if total_req:
            out["serve_adapter_shares"] = {
                k: round(v / total_req, 6)
                for k, v in sorted(adapter_reqs.items())}
    # fedslo request lifecycle (docs/OBSERVABILITY.md): each finished
    # request's serve.request span carries its full host-clock phase
    # breakdown in the B-event args, so the percentiles here are exact
    # over the trace's requests (hand-checkable against the mini-trace
    # golden), not bucket estimates
    req_args = [e.get("args") or {} for e in events
                if e.get("ph") == "B" and e.get("name") == "serve.request"]
    if req_args:
        out["serve_requests"] = len(req_args)

        def _vals(key):
            return sorted(float(a[key]) for a in req_args if key in a)

        def _pct(vals, q):
            # linear interpolation between closest ranks (numpy default)
            if not vals:
                return None
            pos = (len(vals) - 1) * q
            lo = int(pos)
            hi = min(lo + 1, len(vals) - 1)
            return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)

        ttft, e2e, qw = _vals("ttft_s"), _vals("e2e_s"), _vals("queue_s")
        if ttft:
            out["serve_ttft_p50"] = round(_pct(ttft, 0.50), 6)
            out["serve_ttft_p99"] = round(_pct(ttft, 0.99), 6)
        if e2e:
            out["serve_e2e_p99"] = round(_pct(e2e, 0.99), 6)
        if qw:
            out["serve_queue_wait_p99"] = round(_pct(qw, 0.99), 6)
        e2e_total = sum(e2e)
        if e2e_total > 0:
            out["serve_phase_breakdown"] = {
                ph: round(sum(float(a.get(f"{ph}_s", 0.0))
                              for a in req_args) / e2e_total, 6)
                for ph in ("queue", "prefill", "decode")}
    return out


def diff(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    sa, sb = summarize(a), summarize(b)
    out: Dict[str, Any] = {"a_rounds": sa["rounds"], "b_rounds": sb["rounds"],
                           "phases": {}}
    for p in PHASES:
        va, vb = sa["phases"][p], sb["phases"][p]
        na = va / max(sa["rounds"], 1)
        nb = vb / max(sb["rounds"], 1)
        out["phases"][p] = {
            "a_s": round(va, 6), "b_s": round(vb, 6),
            "a_s_per_round": round(na, 6), "b_s_per_round": round(nb, 6),
            "b_vs_a": round(nb / na, 3) if na > 0 else None,
        }
    ra = sa["round_time_total_s"] / max(sa["rounds"], 1)
    rb = sb["round_time_total_s"] / max(sb["rounds"], 1)
    out["round_s_per_round"] = {"a": round(ra, 6), "b": round(rb, 6),
                                "b_vs_a": round(rb / ra, 3) if ra > 0
                                else None}
    return out


# ---------------------------------------------------------------------------
# fedscope: multi-process merge (clock alignment) + critical path + regress
# ---------------------------------------------------------------------------

def _proc_meta(trace: Dict[str, Any], idx: int) -> Dict[str, Any]:
    od = trace.get("otherData") or {}
    return {
        "host": od.get("host", f"host{idx}"),
        "pid": int(od.get("pid", idx)),
        "label": od.get("label") or f"proc{idx}",
        "origin_unix_us": float(od.get("origin_unix_us", 0.0)),
        "trace_id": od.get("trace_id"),
    }


def _comm_pairs(events_a: List[dict], events_b: List[dict]
                ) -> List[Tuple[float, float, str]]:
    """Matched (send_ts, recv_ts, direction) pairs between two processes'
    RAW (per-process clock) events, linked exactly by the propagated span
    ids: a ``comm.recv`` B event's ``parent_span`` names the sender's
    ``comm.send`` span id.  direction is "a2b" or "b2a"."""
    def sends(evs):
        return {e["args"]["span_id"]: e["ts"] for e in evs
                if e.get("ph") == "B" and e.get("name") == "comm.send"
                and isinstance(e.get("args"), dict)
                and "span_id" in e["args"]}

    def recvs(evs):
        return [(e["args"].get("parent_span"), e["ts"]) for e in evs
                if e.get("ph") == "B" and e.get("name") == "comm.recv"
                and isinstance(e.get("args"), dict)]

    pairs = []
    sa, sb = sends(events_a), sends(events_b)
    for parent, ts in recvs(events_b):
        if parent in sa:
            pairs.append((sa[parent], ts, "a2b"))
    for parent, ts in recvs(events_a):
        if parent in sb:
            pairs.append((sb[parent], ts, "b2a"))
    return pairs


def _handshake_offset(meta_ref, events_ref, meta_p, events_p
                      ) -> Tuple[float, str]:
    """Residual clock offset ``d`` (µs) to ADD to process p's unix-mapped
    timestamps so they line up with the reference process.

    NTP-style bound from message causality (send happens-before recv):
    for p→ref messages ``d ≤ recv_ref − send_p``; for ref→p messages
    ``d ≥ send_ref − recv_p``; both in unix µs after applying each
    process's own wall-clock anchor.  The midpoint of the feasible
    interval is the estimate; with traffic in only one direction the
    single bound is used; with none, the raw unix anchors stand."""
    pairs = _comm_pairs(events_p, events_ref)   # a=p, b=ref
    o_p, o_ref = meta_p["origin_unix_us"], meta_ref["origin_unix_us"]
    hi, lo = [], []
    for send_ts, recv_ts, direction in pairs:
        if direction == "a2b":      # p sent, ref received
            hi.append((recv_ts + o_ref) - (send_ts + o_p))
        else:                       # ref sent, p received
            lo.append((send_ts + o_ref) - (recv_ts + o_p))
    if hi and lo:
        return (max(lo) + min(hi)) / 2.0, "handshake"
    if hi:
        return min(hi), "one_way_upper"
    if lo:
        return max(lo), "one_way_lower"
    return 0.0, "unix_clock"


def merge(traces: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge N per-process captures into one timeline.

    Process 0 of the input list is the clock reference (pass the server's
    trace first).  Every process's events are mapped to unix time via its
    exported ``origin_unix_us`` anchor, then refined by the handshake
    estimate above; pids are remapped to the input order so Perfetto
    shows one stable lane per process."""
    procs = []
    for i, tr in enumerate(traces):
        meta = _proc_meta(tr, i)
        evs = [e for e in tr["traceEvents"] if e.get("ph") != "M"]
        procs.append((meta, evs))
    ref_meta, ref_evs = procs[0]
    offsets, methods = [0.0], ["reference"]
    for meta, evs in procs[1:]:
        off, how = _handshake_offset(ref_meta, ref_evs, meta, evs)
        offsets.append(off)
        methods.append(how)

    # merged clock zero = earliest corrected event
    t0 = None
    for (meta, evs), off in zip(procs, offsets):
        for e in evs:
            t = e["ts"] + meta["origin_unix_us"] + off
            t0 = t if t0 is None or t < t0 else t0
    t0 = t0 or 0.0

    merged_events: List[dict] = []
    proc_rows = []
    for i, ((meta, evs), off) in enumerate(zip(procs, offsets)):
        merged_events.append({
            "name": "process_name", "ph": "M", "ts": 0.0, "pid": i,
            "tid": 0, "args": {"name": meta["label"]}})
        for e in evs:
            ne = dict(e)
            ne["ts"] = e["ts"] + meta["origin_unix_us"] + off - t0
            ne["pid"] = i
            merged_events.append(ne)
        proc_rows.append({"label": meta["label"], "host": meta["host"],
                          "pid": meta["pid"],
                          "offset_us": round(offsets[i], 3),
                          "offset_method": methods[i],
                          "trace_id": meta["trace_id"]})
    merged_events.sort(key=lambda e: (e.get("ph") != "M",
                                      e.get("ts", 0.0)))
    return {
        "traceEvents": merged_events,
        "displayTimeUnit": "ms",
        "otherData": {"exporter": "fedtrace merge",
                      "fedscope_merge": {"processes": proc_rows,
                                         "t0_unix_us": round(t0, 3)}},
    }


def _paired_spans(events: List[dict]) -> List[dict]:
    """Complete spans (B/E paired per pid+tid) with the B event's args."""
    open_: Dict[Any, List[dict]] = {}
    spans: List[dict] = []
    for ev in events:
        ph = ev.get("ph")
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            open_.setdefault(key, []).append(ev)
        elif ph == "E":
            stack = open_.get(key, [])
            for i in range(len(stack) - 1, -1, -1):
                if stack[i]["name"] == ev["name"]:
                    b = stack.pop(i)
                    spans.append({
                        "pid": ev.get("pid"), "tid": ev.get("tid"),
                        "name": ev["name"], "t0": b["ts"], "t1": ev["ts"],
                        "args": dict(b.get("args") or {})})
                    break
    return spans


def _proc_labels(trace: Dict[str, Any]) -> Dict[Any, str]:
    labels: Dict[Any, str] = {}
    for e in trace["traceEvents"]:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            labels[e.get("pid")] = (e.get("args") or {}).get(
                "name", str(e.get("pid")))
    return labels


def critical_path(trace: Dict[str, Any],
                  round_idx: Optional[int] = None) -> Dict[str, Any]:
    """Walk each round's span DAG on a merged timeline and name the chain
    that gated the round — phase × process — plus a per-process straggler
    ranking.

    Edges: (1) cross-process ``comm.recv → comm.send`` links from the
    propagated span ids; (2) same-process precedence inside the round
    (the latest span ending inside, or immediately before, the current
    one).  The walk starts at the round's last-finishing span (the server
    combine/round close) and repeatedly follows the predecessor with the
    latest end time — by construction the time-critical chain."""
    events = trace["traceEvents"]
    spans = _paired_spans(events)
    labels = _proc_labels(trace)
    by_id = {s["args"]["span_id"]: s for s in spans
             if "span_id" in s["args"]}

    all_rounds = sorted({int(s["args"]["round"]) for s in spans
                         if isinstance(s["args"].get("round"), (int, float))})
    if round_idx is not None:
        all_rounds = [r for r in all_rounds if r == int(round_idx)]

    def label(s):
        return labels.get(s["pid"], str(s["pid"]))

    out_rounds = []
    for r in all_rounds:
        rs = [s for s in spans if s["args"].get("round") == r]
        if not rs:
            continue
        # terminal = the round's completion span: prefer the driver's
        # "round" span (the combine tier's close); the post-round state
        # sync can land on a silo AFTER it, but that tail is bookkeeping,
        # not the gating chain
        round_spans = [s for s in rs if s["name"] == "round"]
        terminal = max(round_spans or rs, key=lambda s: s["t1"])
        chain, seen = [], set()
        cur = terminal
        while cur is not None and id(cur) not in seen:
            seen.add(id(cur))
            chain.append(cur)
            nxt = None
            parent = cur["args"].get("parent_span")
            if parent in by_id and id(by_id[parent]) not in seen:
                nxt = by_id[parent]
            else:
                # latest same-process round-r span ending inside cur …
                cands = [s for s in rs
                         if s["pid"] == cur["pid"] and id(s) not in seen
                         and cur["t0"] <= s["t1"] <= cur["t1"]]
                if not cands:
                    # … or immediately before it
                    cands = [s for s in rs
                             if s["pid"] == cur["pid"]
                             and id(s) not in seen and s["t1"] <= cur["t0"]]
                if cands:
                    nxt = max(cands, key=lambda s: s["t1"])
            cur = nxt
        chain_rows = [{
            "process": label(s), "name": s["name"],
            "start_s": round(s["t0"] / 1e6, 6),
            "end_s": round(s["t1"] / 1e6, 6),
            "dur_s": round((s["t1"] - s["t0"]) / 1e6, 6),
        } for s in chain]
        gating = next((row["process"] for row in chain_rows
                       if row["process"] != chain_rows[0]["process"]), None)
        # straggler ranking: when does each process finish its OWN
        # round-r work on the merged clock — comm.recv spans are excluded
        # (receiving the post-round sync is waiting, not working), and so
        # is the combine tier itself (it closes every round by
        # construction; the ranking is about who it WAITED for)
        finish: Dict[str, float] = {}
        for s in rs:
            if s["name"] == "comm.recv" or label(s) == label(terminal):
                continue
            lb = label(s)
            finish[lb] = max(finish.get(lb, s["t1"]), s["t1"])
        if not finish:      # single-process trace: rank everyone
            for s in rs:
                lb = label(s)
                finish[lb] = max(finish.get(lb, s["t1"]), s["t1"])
        fastest = min(finish.values())
        stragglers = sorted(
            ({"process": lb, "finish_s": round(t / 1e6, 6),
              "lag_s": round((t - fastest) / 1e6, 6)}
             for lb, t in finish.items()),
            key=lambda row: -row["finish_s"])
        out_rounds.append({"round": r, "chain": chain_rows,
                           "gating_process": gating,
                           "stragglers": stragglers})
    gate_counts: Dict[str, int] = {}
    for row in out_rounds:
        if row["gating_process"]:
            gate_counts[row["gating_process"]] = \
                gate_counts.get(row["gating_process"], 0) + 1
    overall = max(gate_counts, key=gate_counts.get) if gate_counts else None
    return {"rounds": out_rounds, "gating_process_overall": overall}


# -- fedmon offline health report --------------------------------------------

#: per-round fedmon counters replayed into trajectories by ``health``
HEALTH_SERIES = ("health.anomaly_rate", "health.flagged_total",
                 "health.drift_score", "health.round_time_s",
                 "health.staleness_p99")


def health_report(trace: Dict[str, Any]) -> Dict[str, Any]:
    """Offline federation-health report from a captured trace.

    Replays the ``health.*`` counter stream the monitor emitted at every
    verdict (one sample per observed round) plus the ``health.flag``
    events naming each newly flagged client — no jax, no re-detection:
    the report renders what the live monitor concluded, so a silo's
    post-mortem matches what ``/healthz`` served at the time."""
    events = trace["traceEvents"]
    series: Dict[str, List[float]] = {name: [] for name in HEALTH_SERIES}
    flags: List[dict] = []
    for ev in events:
        if ev.get("ph") != "C":
            continue
        name = ev.get("name")
        args = ev.get("args") or {}
        if name in series:
            v = args.get("value")
            if isinstance(v, (int, float)):
                series[name].append(float(v))
        elif name == "health.flag":
            flags.append({k: args[k] for k in
                          ("client", "round", "score", "reason",
                           "staleness") if k in args})
    spans = span_totals(events)
    verdicts = spans.get("health.verdict", {"count": 0, "total_s": 0.0})
    if not (int(verdicts["count"]) or flags
            or any(series[s] for s in series)):
        raise ValueError("trace carries no fedmon events (run with "
                         "health: true + trace: true)")
    out: Dict[str, Any] = {
        "rounds_observed": int(verdicts["count"]),
        "verdict_overhead_s": round(verdicts["total_s"], 6),
        "flags": flags,
        "flagged_clients": sorted({int(f["client"]) for f in flags
                                   if "client" in f}),
    }
    for name, vals in series.items():
        key = name.split(".", 1)[1]
        if vals:
            out[f"{key}_last"] = round(vals[-1], 6)
            out[f"{key}_max"] = round(max(vals), 6)
    return out


def _render_health(h: Dict[str, Any]) -> str:
    lines = [f"rounds observed: {h['rounds_observed']}   "
             f"anomaly rate (last/max): "
             f"{h.get('anomaly_rate_last', 0.0):g}/"
             f"{h.get('anomaly_rate_max', 0.0):g}   "
             f"drift (last/max): {h.get('drift_score_last', 0.0):g}/"
             f"{h.get('drift_score_max', 0.0):g}"]
    if "round_time_s_last" in h:
        lines.append(f"round time (last/max): "
                     f"{h['round_time_s_last']:g}s/"
                     f"{h['round_time_s_max']:g}s")
    if "staleness_p99_last" in h:
        lines.append(f"staleness p99 (last/max): "
                     f"{h['staleness_p99_last']:g}/"
                     f"{h['staleness_p99_max']:g}")
    lines.append(f"flagged clients: {len(h['flagged_clients'])}")
    for f in h["flags"]:
        lines.append(f"  client {f.get('client', '?'):>8}  "
                     f"round {f.get('round', '?'):>5}  "
                     f"score {f.get('score', 0.0):>8.2f}  "
                     f"{f.get('reason', '-')}")
    return "\n".join(lines)


# -- perf-regression gate ----------------------------------------------------

DEFAULT_BANDS_FILE = "BENCH_TOLERANCES.json"


def _dig(obj: Any, path: str) -> Optional[float]:
    cur = obj
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    try:
        return float(cur)
    except (TypeError, ValueError):
        return None


def regress(current: Dict[str, Any], bands: List[Dict[str, Any]],
            trajectory: List[Tuple[str, Dict[str, Any]]]
            ) -> Dict[str, Any]:
    """Compare ``current`` (one bench row) against the committed BENCH
    trajectory under per-metric tolerance bands.

    Each band: ``{"metric": dotted.path, "files": glob,
    "direction": "lower"|"higher", "rel_tol": float,
    "mode": "best"|"last"}``.  A band applies only when the current row
    CARRIES the metric (rows of different archetypes skip each other's
    bands).  Baseline = best (default) or most recent committed value
    among trajectory files matching the glob."""
    results, regressions = [], []
    for band in bands:
        metric = band["metric"]
        cur = _dig(current, metric)
        if cur is None:
            results.append({"metric": metric, "status": "skipped",
                            "reason": "metric absent from current row"})
            continue
        direction = band.get("direction", "lower")
        rel_tol = float(band.get("rel_tol", 0.2))
        mode = band.get("mode", "best")
        pat = band.get("files", "BENCH_r*.json")
        vals = [(name, _dig(row, metric)) for name, row in trajectory
                if fnmatch.fnmatch(os.path.basename(name), pat)]
        vals = [(n, v) for n, v in vals if v is not None]
        if not vals:
            results.append({"metric": metric, "status": "skipped",
                            "reason": f"no committed row matches "
                                      f"{pat!r} with this metric"})
            continue
        if mode == "last":
            base_name, base = vals[-1]
        elif direction == "higher":
            base_name, base = max(vals, key=lambda nv: nv[1])
        else:
            base_name, base = min(vals, key=lambda nv: nv[1])
        if direction == "higher":
            bound = base * (1.0 - rel_tol)
            ok = cur >= bound
        else:
            bound = base * (1.0 + rel_tol)
            ok = cur <= bound
        row = {"metric": metric, "status": "ok" if ok else "REGRESSION",
               "current": cur, "baseline": base,
               "baseline_file": os.path.basename(base_name),
               "bound": round(bound, 6), "direction": direction,
               "rel_tol": rel_tol}
        results.append(row)
        if not ok:
            regressions.append(row)
    return {"checked": sum(1 for r in results if r["status"] != "skipped"),
            "results": results, "regressions": regressions,
            "ok": not regressions}


def load_bands(path: str) -> List[Dict[str, Any]]:
    with open(path) as fh:
        data = json.load(fh)
    bands = data["bands"] if isinstance(data, dict) else data
    if not isinstance(bands, list):
        raise ValueError(f"{path}: expected a list (or {{'bands': [...]}})")
    return bands


def load_trajectory(baseline_dir: str
                    ) -> List[Tuple[str, Dict[str, Any]]]:
    rows = []
    for name in sorted(glob_mod.glob(
            os.path.join(baseline_dir, "BENCH_r*.json"))):
        try:
            with open(name) as fh:
                rows.append((name, json.load(fh)))
        except (OSError, json.JSONDecodeError):
            continue
    return rows


def _render_summary(s: Dict[str, Any]) -> str:
    lines = [f"rounds: {s['rounds']}   "
             f"round wall-clock: {s['round_time_total_s']:.4f}s   "
             f"compiles: {s['compile_count']} ({s['compile_s']:.2f}s)"]
    if "collective_bytes_per_round" in s:
        axis = ""
        if "collective_bytes_client_per_round" in s:
            stage = ""
            if s.get("collective_bytes_stage_per_round", 0.0):
                stage = (f" + stage "
                         f"{s['collective_bytes_stage_per_round']:.0f}")
            axis = (f" (client "
                    f"{s['collective_bytes_client_per_round']:.0f}"
                    f"{stage} + model "
                    f"{s.get('collective_bytes_model_per_round', 0.0):.0f})")
        lines.append(
            f"collective bytes/round: "
            f"{s['collective_bytes_per_round']:.0f}{axis}   "
            f"quant error norm (last): "
            f"{s.get('quant_error_norm_last', 0.0):g}")
    if "population_members" in s:
        lines.append(
            f"population: {s['population_members']} members   "
            f"member loss best/worst (last): "
            f"{s['member_loss_best_last']:g}/"
            f"{s['member_loss_worst_last']:g}   "
            f"bytes spread: {s['member_bytes_spread_max']:g}")
    if "page_in_bytes" in s or "page_hit_rate" in s:
        lines.append(
            f"store paging: {s.get('page_in_bytes', 0.0):.0f} B paged in   "
            f"hit rate {s.get('page_hit_rate', 0.0):g}   "
            f"writeback lag {s.get('writeback_lag_rounds', 0.0):g} rounds")
    if "buffer_occupancy_last" in s:
        lines.append(
            f"async buffer: occupancy (last) "
            f"{s['buffer_occupancy_last']:g}   staleness p50/p99 "
            f"{s.get('staleness_p50', 0.0):g}/"
            f"{s.get('staleness_p99', 0.0):g}   dropped "
            f"{s.get('async_updates_dropped', 0.0):g}   sim clock "
            f"{s.get('async_sim_time_s', 0.0):g}s")
    if "comm_retries_total" in s or "quorum_trajectory" in s:
        traj = s.get("quorum_trajectory", [])
        lines.append(
            f"fedguard: {s.get('comm_retries_total', 0.0):g} retries "
            f"(rate {s.get('comm_retry_rate_last', 0.0):g})   quorum "
            f"{'-'.join(str(q) for q in traj) or '?'}   dead ranks "
            f"(last) {s.get('dead_ranks_last', 0.0):g}   deduped "
            f"{s.get('comm_dup_dropped', 0.0):g}")
    if "serve_admits" in s or "serve_adapter_requests" in s:
        ad = s.get("serve_adapter_requests", {})
        lines.append(
            f"serving: {s.get('serve_admits', 0)} admits   "
            f"queue depth (last) {s.get('serve_queue_depth_last', 0.0):g}   "
            f"tokens/s (last) {s.get('serve_tokens_per_s_last', 0.0):g}   "
            f"{len(ad)} adapters / {sum(ad.values())} requests")
    if "serve_requests" in s:
        pb = s.get("serve_phase_breakdown", {})
        lines.append(
            f"serve slo: {s['serve_requests']} requests   ttft p50/p99 "
            f"{s.get('serve_ttft_p50', 0.0):g}/"
            f"{s.get('serve_ttft_p99', 0.0):g}s   e2e p99 "
            f"{s.get('serve_e2e_p99', 0.0):g}s   queue p99 "
            f"{s.get('serve_queue_wait_p99', 0.0):g}s   phases "
            + "/".join(f"{p} {pb.get(p, 0.0):.0%}"
                       for p in ("queue", "prefill", "decode")))
    if s.get("device_phase_source") == "measured":
        lines.append("device phases: MEASURED (trace_device probe; "
                     "FLOP proxy deltas "
                     + ", ".join(f"{p} {d:+.3f}"
                                 for p, d in s["device_phase_delta"]
                                 .items()) + ")")
    lines.append(f"{'phase':<16}{'seconds':>12}{'share':>9}")
    total = sum(s["phases"].values()) or 1.0
    for p in PHASES:
        v = s["phases"][p]
        lines.append(f"{p:<16}{v:>12.4f}{100.0 * v / total:>8.1f}%")
    if s.get("spans"):
        lines.append("spans:")
        for n, row in s["spans"].items():
            lines.append(f"  {n:<22}x{row['count']:<6}"
                         f"{row['total_s']:.4f}s")
    return "\n".join(lines)


def _render_diff(d: Dict[str, Any]) -> str:
    lines = [f"{'phase':<16}{'A s/round':>12}{'B s/round':>12}{'B/A':>8}"]
    for p in PHASES:
        row = d["phases"][p]
        ratio = row["b_vs_a"]
        lines.append(f"{p:<16}{row['a_s_per_round']:>12.5f}"
                     f"{row['b_s_per_round']:>12.5f}"
                     f"{ratio if ratio is not None else '-':>8}")
    r = d["round_s_per_round"]
    lines.append(f"{'round (total)':<16}{r['a']:>12.5f}{r['b']:>12.5f}"
                 f"{r['b_vs_a'] if r['b_vs_a'] is not None else '-':>8}")
    return "\n".join(lines)


def _render_critical_path(cp: Dict[str, Any]) -> str:
    lines = []
    for row in cp["rounds"]:
        lines.append(f"round {row['round']}: gated by "
                     f"{row['gating_process'] or '(single process)'}")
        for link in row["chain"]:
            lines.append(f"  <- {link['process']:<10}{link['name']:<14}"
                         f"{link['dur_s']:>10.4f}s  "
                         f"(ends {link['end_s']:.4f}s)")
        lines.append("  stragglers: " + "  ".join(
            f"{s['process']}+{s['lag_s']:.4f}s"
            for s in row["stragglers"]))
    lines.append(f"gating process overall: "
                 f"{cp['gating_process_overall'] or '-'}")
    return "\n".join(lines)


def _render_regress(r: Dict[str, Any]) -> str:
    lines = [f"{'metric':<42}{'status':<12}{'current':>12}{'baseline':>12}"
             f"{'bound':>12}"]
    for row in r["results"]:
        if row["status"] == "skipped":
            lines.append(f"{row['metric']:<42}{'skipped':<12}  "
                         f"({row['reason']})")
        else:
            lines.append(f"{row['metric']:<42}{row['status']:<12}"
                         f"{row['current']:>12.4f}{row['baseline']:>12.4f}"
                         f"{row['bound']:>12.4f}")
    lines.append(f"{r['checked']} checked, {len(r['regressions'])} "
                 f"regression(s)")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="fedtrace", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = parser.add_subparsers(dest="cmd")
    p_sum = sub.add_parser("summarize", help="per-phase breakdown of one "
                                             "trace")
    p_sum.add_argument("trace")
    p_sum.add_argument("--json", action="store_true")
    p_diff = sub.add_parser("diff", help="compare two traces per phase")
    p_diff.add_argument("trace_a")
    p_diff.add_argument("trace_b")
    p_diff.add_argument("--json", action="store_true")
    p_merge = sub.add_parser(
        "merge", help="align N per-process captures into one timeline "
                      "(pass the server's trace first — it is the clock "
                      "reference)")
    p_merge.add_argument("traces", nargs="+")
    p_merge.add_argument("--out", required=True)
    p_merge.add_argument("--json", action="store_true")
    p_cp = sub.add_parser(
        "critical-path", help="per-round gating chain + straggler "
                              "ranking of a merged timeline")
    p_cp.add_argument("trace")
    p_cp.add_argument("--round", type=int, default=None)
    p_cp.add_argument("--json", action="store_true")
    p_health = sub.add_parser(
        "health", help="offline fedmon federation-health report from a "
                       "captured trace")
    p_health.add_argument("trace")
    p_health.add_argument("--json", action="store_true")
    p_reg = sub.add_parser(
        "regress", help="tolerance-band gate of a bench row vs the "
                        "committed BENCH_r*.json trajectory (exit 3 on "
                        "regression)")
    p_reg.add_argument("current")
    p_reg.add_argument("--bands", default=None)
    p_reg.add_argument("--baseline-dir", default=None)
    p_reg.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)

    if args.cmd is None:
        parser.print_usage(sys.stderr)
        return 2
    try:
        if args.cmd == "summarize":
            s = summarize(load_trace(args.trace))
            print(json.dumps(s) if args.json else _render_summary(s))
        elif args.cmd == "diff":
            d = diff(load_trace(args.trace_a), load_trace(args.trace_b))
            print(json.dumps(d) if args.json else _render_diff(d))
        elif args.cmd == "merge":
            merged = merge([load_trace(p) for p in args.traces])
            with open(args.out, "w") as fh:
                json.dump(merged, fh)
            info = merged["otherData"]["fedscope_merge"]
            if args.json:
                print(json.dumps(info))
            else:
                for row in info["processes"]:
                    print(f"{row['label']:<12}{row['host']}:{row['pid']}"
                          f"  offset {row['offset_us']:+.1f}us "
                          f"({row['offset_method']})")
                print(f"wrote {args.out}")
        elif args.cmd == "critical-path":
            cp = critical_path(load_trace(args.trace),
                               round_idx=args.round)
            print(json.dumps(cp) if args.json else
                  _render_critical_path(cp))
        elif args.cmd == "health":
            h = health_report(load_trace(args.trace))
            print(json.dumps(h) if args.json else _render_health(h))
        else:  # regress
            base_dir = args.baseline_dir or os.path.dirname(
                os.path.abspath(args.current)) or "."
            bands_path = args.bands or os.path.join(base_dir,
                                                    DEFAULT_BANDS_FILE)
            with open(args.current) as fh:
                current = json.load(fh)
            r = regress(current, load_bands(bands_path),
                        load_trajectory(base_dir))
            print(json.dumps(r) if args.json else _render_regress(r))
            if not r["ok"]:
                return 3
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
        print(f"fedtrace: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
