"""The harness: one run of one cell.

``main`` loads the cell named on the command line (``workloads/<name>.json``),
its configuration (``configs/<config>.json``) and its driver
(``drivers/<driver>.py``), sets up, warms, measures for ``--seconds``, reads the
peak memory, frees the program, lets the driver compare what the timed path
produced with the plain reference, and prints the contract's last line.  With
``--trace 1`` the window runs under the profiler, and every file of
``layer_metrics/`` that lists the cell is read by its reader.

Nothing here knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import importlib
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional, Tuple

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(Exception):
    """The run cannot be made (no chip, a file missing): no result is printed."""


def load_json(*parts: str) -> dict:
    path = os.path.join(BENCH, *parts)
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


class Run:
    """What a driver and the readers see of one run."""

    def __init__(self, cell: dict, cfg: dict, seed: int, seconds: float,
                 peak: dict):
        self.cell, self.cfg, self.seed = cell, cfg, int(seed)
        self.seconds, self.peak = float(seconds), peak
        self.host_spans: List[Tuple[str, float, float]] = []
        self.counters: Dict[str, float] = {}
        self.window: Optional[Tuple[float, float]] = None
        self.window_s = 0.0
        self.trace = None
        self.clock_offset_ns: Optional[float] = None
        self.compilations = 0

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.host_spans.append((name, t0, time.perf_counter()))

    def note(self, **kw) -> None:
        """Facts for the earlier lines of the output, not for the last one."""
        print(json.dumps({"note": kw}, default=str), flush=True)


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def find_devices(chips: int, peaks: dict):
    """The chips the cell asks for, or an error: no fallback to the CPU."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise BenchError(f"needs a TPU, jax found platform {d0.platform!r}")
    if d0.device_kind not in peaks:
        raise BenchError(f"device kind {d0.device_kind!r} is not in peaks.json")
    if len(devices) < chips:
        raise BenchError(f"needs {chips} chips, jax found {len(devices)}")
    return devices[:chips], peaks[d0.device_kind]


def place_compile_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``: a
    fixed path, so the second run of a cell in a checkout compiles nothing."""
    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return ""          # the tests: XLA:CPU programs compile in seconds
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def layer_metric_files(cell_name: str) -> List[dict]:
    """The per-layer metrics of this cell: every file of ``layer_metrics/``
    that lists the cell under ``workloads``."""
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if cell_name in m["workloads"]:
            out.append(m)
    return out


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def program_spans(window: Tuple[float, float], origin: float) -> List[Tuple[str, float, float]]:
    """The program's own tracer events as (name, start_s, end_s) on
    ``time.perf_counter``, those that end inside the window."""
    from fedml_tpu.obs import get_tracer
    open_: Dict[tuple, list] = {}
    out = []
    for ev in get_tracer().events():
        key = (ev.get("tid"), ev["name"])
        if ev["ph"] == "B":
            open_.setdefault(key, []).append(ev["ts"])
        elif ev["ph"] == "E" and open_.get(key):
            t0 = origin + open_[key].pop() / 1e6
            t1 = origin + ev["ts"] / 1e6
            if window[0] <= t1 <= window[1]:
                out.append((ev["name"], t0, t1))
    return out


def run_cell(cell: dict, cfg: dict, seed: int, seconds: float, trace: bool,
             devices, peak: dict) -> dict:
    import jax

    if trace:
        seconds = min(seconds, float(cell.get("trace_seconds", seconds)))
    run = Run(cell, cfg, seed, seconds, peak)
    driver = importlib.import_module(f"drivers.{cell['driver']}")

    def on_compile(event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            run.compilations += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    log(f"set-up of {cell['name']} (seed {seed})")
    state = driver.setup(run)
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    origin = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        from fedml_tpu import obs
        origin = time.perf_counter()
        obs.configure(enabled=True, reset=True, jax_hooks=False)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiled_before = run.compilations
    setup_s = time.perf_counter() - T_START
    log(f"window of {seconds:g} s after {setup_s:.1f} s of set-up")
    with jax.profiler.TraceAnnotation("bench.window") if trace \
            else contextlib.nullcontext():
        t0 = time.perf_counter()
        driver.window(state, run, seconds)
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    # what is still open at the close is waited for outside the traced window
    result = driver.finish(state, run)
    run.window = result.get("window", (t0, t1))
    run.window_s = run.window[1] - run.window[0]
    if trace:
        run.host_spans += program_spans(run.window, origin)
        from fedml_tpu import obs
        obs.configure(enabled=False)
    in_window = run.compilations - compiled_before
    memory = peak_memory(devices)
    run.note(compilations_in_window=in_window, memory_peak_bytes=memory,
             window_s=run.window_s, **result.get("notes", {}))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    breakdown = None
    if trace:
        from readers import xplane
        t_read = time.perf_counter()
        run.trace = xplane.Trace.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if run.trace is None or not run.trace.devices:
            raise BenchError("the traced run left no device trace to read")
        if run.trace.window:      # the annotation opened at t0 on both clocks
            run.clock_offset_ns = run.trace.window[0] - t0 * 1e9
            run.trace.window = (run.window[0] * 1e9 + run.clock_offset_ns,
                                run.window[1] * 1e9 + run.clock_offset_ns)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.time_by_op(10),
                     "idle_gaps": run.trace.gaps_by_host_span(
                         run.host_spans, run.clock_offset_ns or 0.0, 10)}
        metrics = {}
        for m in layer_metric_files(cell["name"]):
            reader = importlib.import_module(f"readers.{m['reader']}")
            value = reader.read(m.get("args", {}), run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        log(f"trace read in {time.perf_counter() - t_read:.1f} s")
    else:
        for name, (value, unit) in result["metrics"].items():
            metrics[name] = {"value": float(value), "unit": unit}

    log("comparison with the plain reference")
    t_check = time.perf_counter()
    gc.collect()
    checks = driver.check(state, run, result)
    run.note(check_s=time.perf_counter() - t_check)
    if in_window:
        checks["compilations_in_window"] = {"value": in_window, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})"
              f"{'' if c['value'] <= c['limit'] else '  <-- FAILS'}",
              file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def main(argv=None, find=find_devices) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        cell = load_json("workloads", f"{opts.workload}.json")
        cfg = load_json("configs", f"{cell['config']}.json")
        peaks = load_json("peaks.json")
        if not os.path.isdir(os.path.join(ROOT, "fedml_tpu")):
            raise BenchError("the system under test (fedml_tpu/) is not in this checkout")
        place_compile_cache()
        devices, peak = find(int(cell.get("chips", 1)), peaks)
        line = run_cell(cell, cfg, opts.seed, opts.seconds, bool(opts.trace),
                        devices, peak)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(line), flush=True)
    return 0
