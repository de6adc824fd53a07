"""What the program's own spans say (``fedml_tpu.obs``'s tracer, which the
harness switches on for the traced window and leaves in memory): the mean of
an argument over the spans of one name, the mean length of some spans per span
of another, and, as notes, whether the one anchor between the tracer's clock
and the profiler's holds, the longest span of a name, and the device's idle
time under each span.

A reader here takes ``(args, run)`` and returns a number or ``None``; a program
that has no such spans (an older one) gives ``None`` and no note.
"""

from __future__ import annotations

import bisect
import fnmatch
from typing import Dict, List, Optional, Tuple


def paired(events: List[dict], origin_s: float) -> List[dict]:
    """The tracer's ``B``/``E`` events as spans on ``time.perf_counter``:
    ``{"name", "tid", "t0", "t1", "args", "id", "parent"}``, the args of both
    events merged.  A span the tracer had to close itself is left out."""
    open_: Dict[tuple, list] = {}
    out = []
    for ev in events:
        key = (ev.get("tid"), ev["name"])
        if ev["ph"] == "B":
            open_.setdefault(key, []).append(ev)
        elif ev["ph"] == "E" and open_.get(key):
            b = open_[key].pop()
            args = {**b.get("args", {}), **ev.get("args", {})}
            if args.pop("synthesized_end", False):
                continue
            out.append({"name": ev["name"], "tid": ev.get("tid"), "t0": origin_s + b["ts"] / 1e6,
                        "t1": origin_s + ev["ts"] / 1e6, "args": args,
                        "id": args.pop("span_id", None),
                        "parent": args.pop("parent", None)})
    return out


def in_window(spans: List[dict], window: Tuple[float, float]) -> List[dict]:
    """Those that end inside the window."""
    return [s for s in spans if window[0] <= s["t1"] <= window[1]]


def clock_check(spans: List[dict], host_events, pattern: str, offset_ns: float) -> dict:
    """Every host-runtime event whose name matches ``pattern`` has to begin
    inside one of ``spans``, both on the trace's clock, from the first of the
    spans on (what was dispatched before the tracer was switched on has no
    span).  ``worst_us``: the farthest such an event began from the nearest
    span, 0 if none began outside."""
    rows = sorted((s["t0"] * 1e9 + offset_ns, s["t1"] * 1e9 + offset_ns) for s in spans)
    if not rows:
        return {"events": 0, "outside": 0, "worst_us": 0.0}
    n = outside = 0
    worst = 0.0
    starts = [t0 for t0, _ in rows]
    for name, start, _ in host_events:
        if not fnmatch.fnmatchcase(name, pattern) or not rows[0][0] <= start <= rows[-1][1]:
            continue
        n += 1
        i = bisect.bisect_right(starts, start) - 1      # the last span begun by then
        if start > rows[i][1]:
            outside += 1
            nearest = [start - rows[i][1]] + [t0 - start for t0, _ in rows[i + 1:i + 2]]
            worst = max(worst, min(nearest))
    return {"events": n, "outside": outside, "worst_us": worst / 1e3}


def longest(spans: List[dict], by_id: Dict[str, dict], index: Optional[str]) -> Optional[dict]:
    """The longest of ``spans`` with, under ``index``, that argument of its
    nearest ancestor that carries it (a tick's read-back: the iteration)."""
    if not spans:
        return None
    top = max(spans, key=lambda s: s["t1"] - s["t0"])
    out = {"span": top["name"], "ms": (top["t1"] - top["t0"]) * 1e3}
    at = top
    while index and at is not None:
        if index in at["args"]:
            out[index] = at["args"][index]
            break
        at = by_id.get(at["parent"])
    return out


def stretches(spans: List[dict]) -> List[Tuple[float, float, str]]:
    """One thread's nested spans cut into stretches that do not overlap, each
    under the name of the innermost span open in it, in time order (seconds
    on ``time.perf_counter``)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []       # (end, name) of the spans open
    at = 0.0

    def close(until: float) -> None:
        nonlocal at
        if until > at:
            out.append((at, until, stack[-1][1]))
            at = until

    for s in sorted(spans, key=lambda s: (s["t0"], -s["t1"])):
        while stack and stack[-1][0] <= s["t0"]:
            close(stack[-1][0])
            stack.pop()
        if stack:
            close(s["t0"])
        at = s["t0"]
        stack.append((s["t1"], s["name"]))
    while stack:
        close(stack[-1][0])
        stack.pop()
    return out


def idle_by_span(spans: List[dict], gaps, offset_ns: float) -> List[list]:
    """Seconds of the device's idle gaps that passed while the thread of
    ``spans`` was inside each of them (the innermost counts), and under
    ``"(no span)"`` those that passed outside them all."""
    rows = [(t0 * 1e9 + offset_ns, t1 * 1e9 + offset_ns, name)
            for t0, t1, name in stretches(spans)]
    acc: Dict[str, float] = {}
    i = 0
    for g0, g1 in gaps:                       # both in time order: one sweep
        while i < len(rows) and rows[i][1] <= g0:
            i += 1
        left, j = g1 - g0, i
        while j < len(rows) and rows[j][0] < g1:
            inside = min(g1, rows[j][1]) - max(g0, rows[j][0])
            acc[rows[j][2]] = acc.get(rows[j][2], 0.0) + inside / 1e9
            left -= inside
            j += 1
        acc["(no span)"] = acc.get("(no span)", 0.0) + left / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]


def span_ms(spans: List[dict]) -> List[list]:
    """``[name, count, mean ms, mean self ms]`` of each span name, the most
    self time first; a span's self time is its length less its children's."""
    kids: Dict[str, float] = {}
    for s in spans:
        if s["parent"] is not None:
            kids[s["parent"]] = kids.get(s["parent"], 0.0) + s["t1"] - s["t0"]
    acc: Dict[str, list] = {}
    for s in spans:
        row = acc.setdefault(s["name"], [0, 0.0, 0.0])
        row[0] += 1
        row[1] += s["t1"] - s["t0"]
        row[2] += s["t1"] - s["t0"] - kids.get(s["id"], 0.0)
    return [[k, n, total / n * 1e3, own / n * 1e3]
            for k, (n, total, own) in sorted(acc.items(), key=lambda kv: -kv[1][2])]


def notes(args: dict, spans: List[dict], live: List[dict], run) -> None:
    """``args["notes"]``: ``clock_check`` (``events``: a pattern of host-runtime
    event names, ``span``: the span each has to begin in), ``longest``
    (``span``, ``index``), ``idle_by_span`` (a span's name: over the spans of
    the thread that opens it), ``span_ms`` (true: over the spans of the
    program's threads; the tracer's synthetic lanes, whose ``tid`` is negative,
    hold lifetimes written afterwards, not what a thread was doing)."""
    want = args.get("notes", {})
    trace, off = getattr(run, "trace", None), getattr(run, "clock_offset_ns", None)
    out = {}
    if "clock_check" in want and trace is not None and off is not None:
        c = want["clock_check"]
        out["clock_check"] = clock_check([s for s in spans if s["name"] == c["span"]],
                                         trace.host_events, c["events"], off)
    if "longest" in want:
        c = want["longest"]
        out["longest"] = longest([s for s in live if s["name"] == c["span"]],
                                 {s["id"]: s for s in spans}, c.get("index"))
    if "idle_by_span" in want and trace is not None and off is not None and trace.devices:
        tids = [s["tid"] for s in spans if s["name"] == want["idle_by_span"]]
        out["idle_by_span"] = idle_by_span(
            [s for s in spans if tids and s["tid"] == tids[0]], trace.gaps(), off)
    if want.get("span_ms"):
        out["span_ms"] = span_ms([s for s in live if (s["tid"] or 0) > 0])
    if out:
        run.note(**out)


def read(args: dict, run) -> Optional[float]:
    """``args["kind"]``:

    - ``arg_mean``: the mean of the argument ``arg`` over the spans named
      ``span`` (those that carry it);
    - ``mean_ms``: the summed length of the spans named in ``names``, over the
      number of spans named ``per``, in ms.

    Spans that end inside the run's window count."""
    from fedml_tpu.obs import get_tracer
    tracer = get_tracer()
    origin = getattr(tracer, "origin_s", None)
    if origin is None or run.window is None:
        return None
    spans = paired(tracer.events(), origin)
    live = in_window(spans, run.window)
    if not live:
        return None
    notes(args, spans, live, run)
    kind = args["kind"]
    if kind == "arg_mean":
        values = [s["args"][args["arg"]] for s in live
                  if s["name"] == args["span"] and args["arg"] in s["args"]]
        return sum(values) / len(values) if values else None
    if kind == "mean_ms":
        per = sum(1 for s in live if s["name"] == args["per"])
        parts = [s["t1"] - s["t0"] for s in live if s["name"] in args["names"]]
        return sum(parts) / per * 1e3 if per and parts else None
    raise ValueError(f"spans reader: unknown kind {kind!r}")
