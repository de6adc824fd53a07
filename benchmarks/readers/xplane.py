"""Reduction of the profiler's trace (``*.xplane.pb``) to what the per-layer
metrics read: the device's busy union and idle gaps inside the traced window,
self time by operation, the duration of whole programs, and the gaps
attributed to the host span that was open at the time.

A reader here takes ``(args, run)`` and returns a number or ``None``; ``None``
means that there was nothing to read, and the metric is left out of the line.
"""

from __future__ import annotations

import bisect
import fnmatch
import glob
import importlib
import os
from typing import Dict, Iterable, List, Optional, Tuple

#: the host-side annotation the harness opens around the measured window; it
#: ties the trace's clock to ``time.perf_counter``
WINDOW = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: what the host runtime's own events say the host was doing (their names
#: start so); they join the benchmark's and the program's spans when an idle
#: gap is attributed
HOST_EVENTS = ("PjitFunction(", "np.asarray(jax.Array)", "DevicePut", "shard_args",
               "PythonRefManager::CollectGarbage")


def short_name(hlo: str) -> str:
    """``%fusion.9 = f32[32,128]{1,0:T(8,128)} fusion(...), kind=kOutput`` ->
    ``fusion.9 f32[32,128] fusion``: the profiler names an operation by its
    whole HLO text on this chip."""
    if " = " not in hlo:
        return hlo[:80]
    name, rest = hlo.split(" = ", 1)
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if not rest.startswith("(") else "(tuple)"
    body = rest.split(") ", 1)[-1] if rest.startswith("(") else rest.split(" ", 1)[-1]
    op = body.split("(", 1)[0].strip()
    return f"{name.lstrip('%')} {shape} {op}"[:80]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: List[dict]) -> None:
    """Adds ``self_ns`` to each event of one line: its duration less the part
    its children cover (a ``while`` spans the operations of its body)."""
    stack: List[dict] = []
    for ev in sorted(events, key=lambda e: (e["start"], -e["dur"])):
        ev["self_ns"] = ev["dur"]
        while stack and ev["start"] >= stack[-1]["start"] + stack[-1]["dur"]:
            stack.pop()
        if stack:
            stack[-1]["self_ns"] -= min(
                ev["dur"], stack[-1]["start"] + stack[-1]["dur"] - ev["start"])
        stack.append(ev)


class Trace:
    """One trace, reduced once.  Times are nanoseconds on the trace's clock."""

    def __init__(self, profile_data):
        self.devices: Dict[str, dict] = {}
        self.window: Optional[Tuple[float, float]] = None
        self.host_events: List[Tuple[str, float, float]] = []
        for plane in profile_data.planes:
            name = plane.name
            if name.startswith("/device:") and "TPU" in name:
                dev = {"ops": [], "modules": []}
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        dev["ops" if line.name == OPS_LINE else "modules"] = \
                            self._events(line)
                if dev["ops"] or dev["modules"]:
                    self_times(dev["ops"])
                    self.devices[name] = dev
            elif name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == WINDOW:
                            self.window = (e.start_ns, e.start_ns + e.duration_ns)
                        elif e.name.startswith(HOST_EVENTS):
                            self.host_events.append(
                                (e.name, e.start_ns, e.start_ns + e.duration_ns))
        if self.window is None:
            spans = [(e["start"], e["start"] + e["dur"])
                     for d in self.devices.values() for e in d["ops"] + d["modules"]]
            if spans:
                self.window = (min(s for s, _ in spans), max(e for _, e in spans))

    @staticmethod
    def _events(line) -> List[dict]:
        return [{"name": e.name, "start": float(e.start_ns), "dur": float(e.duration_ns)}
                for e in line.events]

    @classmethod
    def from_dir(cls, directory: str) -> Optional["Trace"]:
        files = sorted(glob.glob(os.path.join(
            directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            return None
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(files[-1]))

    # -- busy and idle -------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    def _busy(self, dev: dict) -> List[Tuple[float, float]]:
        w0, w1 = self.window
        if dev.get("busy_for") != self.window:
            source = dev["ops"] or dev["modules"]
            dev["busy"] = union((max(e["start"], w0), min(e["start"] + e["dur"], w1))
                                for e in source
                                if e["start"] < w1 and e["start"] + e["dur"] > w0)
            dev["busy_for"] = self.window
        return dev["busy"]

    def busy_s(self) -> float:
        """Seconds inside the window in which an operation ran, averaged over
        the chips that ran any."""
        if not self.devices or not self.window:
            return 0.0
        per = [sum(e - s for s, e in self._busy(d)) for d in self.devices.values()]
        return sum(per) / len(per) / 1e9

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the first device inside the window."""
        if not self.devices or not self.window:
            return []
        busy = self._busy(next(iter(self.devices.values())))
        edges = [self.window[0]] + [t for iv in busy for t in iv] + [self.window[1]]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def busy_between(self, t0: float, t1: float) -> float:
        """Busy nanoseconds of the first device between two trace times."""
        busy = self._busy(next(iter(self.devices.values())))
        lo = bisect.bisect_left(busy, (t0, t0)) - 1
        total = 0.0
        for s, e in busy[max(lo, 0):]:
            if s >= t1:
                break
            total += max(0.0, min(e, t1) - max(s, t0))
        return total

    # -- by operation and program ----------------------------------------
    def _ops_in_window(self):
        w0, w1 = self.window
        for dev in self.devices.values():
            for e in dev["ops"]:
                if w0 <= e["start"] < w1:
                    yield e

    def time_by_op(self, top: int = 10) -> List[list]:
        n = max(len(self.devices), 1)
        acc: Dict[str, float] = {}
        for e in self._ops_in_window():
            acc[e["name"]] = acc.get(e["name"], 0.0) + e["self_ns"]
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[short_name(k), v / n / 1e9] for k, v in rows]

    def module_times(self, pattern: str) -> List[float]:
        """Device seconds of each run, inside the window, of the programs
        whose name matches ``pattern``."""
        w0, w1 = self.window
        dev = next(iter(self.devices.values()), None)
        if dev is None:
            return []
        return [e["dur"] / 1e9 for e in dev["modules"]
                if fnmatch.fnmatchcase(e["name"], pattern) and w0 <= e["start"] < w1]

    # -- gaps by what the host was doing -----------------------------------------
    def gaps_by_host_span(self, host_spans: List[Tuple[str, float, float]],
                          clock_offset_ns: float, top: int = 10) -> List[list]:
        """``host_spans`` are (name, start_s, end_s) on ``time.perf_counter``;
        ``clock_offset_ns`` is trace time minus perf_counter time (from the
        window annotation).  Each gap goes to the shortest span open at its
        middle, or to ``"(no span)"``."""
        acc: Dict[str, float] = {}
        spans = sorted([(s * 1e9 + clock_offset_ns, e * 1e9 + clock_offset_ns, n)
                        for n, s, e in host_spans] +
                       [(s, e, n) for n, s, e in self.host_events])
        nxt, active = 0, []              # a sweep: gaps come in time order
        for g0, g1 in self.gaps():
            mid = (g0 + g1) / 2
            while nxt < len(spans) and spans[nxt][0] <= mid:
                active.append(spans[nxt])
                nxt += 1
            active = [sp for sp in active if sp[1] > mid]
            name = min(active, key=lambda sp: sp[1] - sp[0])[2] if active else "(no span)"
            acc[name] = acc.get(name, 0.0) + (g1 - g0) / 1e9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def _roofline(path: str):
    module, func = path.rsplit(".", 1)
    return getattr(importlib.import_module(f"rooflines.{module}"), func)


# -- readers -----------------------------------------------------------------

def read(args: dict, run) -> Optional[float]:
    """``args["kind"]`` picks the reduction:

    - ``idle_pct``: 100 * (1 - busy union / traced window);
    - ``span_minus_busy_ms``: mean over the host spans named ``span`` of their
      length less the device-busy time inside them, in ms;
    - ``module_roofline_pct``: ``roofline`` (a function of ``rooflines/``, given
      the configuration, the cell, the run's counters and the chip's peaks)
      returns the least seconds the chip could take for one run of the programs
      matching ``module``, over the mean device time of one such run.
    """
    trace = run.trace
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    kind = args["kind"]
    if kind == "idle_pct":
        busy = trace.busy_s()
        return 100.0 * (1.0 - busy / trace.window_s) if busy > 0 else None
    if kind == "span_minus_busy_ms":
        rows = [(s, e) for n, s, e in run.host_spans if n == args["span"]]
        if not rows or run.clock_offset_ns is None:
            return None
        off = run.clock_offset_ns
        host = [(e - s) * 1e3 - trace.busy_between(s * 1e9 + off, e * 1e9 + off) / 1e6
                for s, e in rows]
        return sum(host) / len(host)
    peak = run.peak
    if kind == "module_roofline_pct":
        times = trace.module_times(args["module"])
        if not times:
            return None
        least = _roofline(args["roofline"])(run.cfg, run.cell, run.counters, peak)
        return 100.0 * least / (sum(times) / len(times)) if least else None
    raise ValueError(f"xplane reader: unknown kind {kind!r}")
