"""Device time by module: every operation of the traced window joined to the
module of the model it belongs to, through the instruction -> module maps the
program under test publishes (``fedml_tpu.obs.programs``: only the process
that compiled a program can name its instructions).

Every event of the trace's ``XLA Ops`` line is given to the ``XLA Modules``
event that contains its start (instruction names repeat across programs:
``fusion.12`` is in both), looked up in that program's map, and its self time
(``readers/xplane.py``'s: a ``while`` less its body) is summed.  The join is
made once a run, after the window has closed, and printed once on a
``{"note": ...}`` line: ``by_module`` (for each program its runs in the
window, their mean ``XLA Modules`` time, and every group of paths with its ms
a run), ``unmatched_pct`` (device time of the window whose instruction was in
no map or whose event lay in no program) and ``map_s`` (the seconds the maps
took: each is a lowering and a compile, or a load from the compile cache).

A reader here takes ``(args, run)`` and returns a number or ``None``; a
program that publishes no map (an older one) gives ``None`` and no note.
"""

from __future__ import annotations

import bisect
import fnmatch
import re
import time
from typing import Dict, List, Optional

from readers import xplane

#: how far down a path a group of ``by_module`` goes
GROUP_DEPTH = 3
_INDEX = re.compile(r"(?<=_)\d+$")
_PROGRAM = re.compile(r"^(?:jit_)?(?P<name>.+?)(?:\(\d+\))?$")


def instruction(event_name: str) -> str:
    """``%fusion.9 = f32[32,128]{...} fusion(...)`` -> ``fusion.9``: the
    profiler names an operation by its whole HLO text on this chip."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def program_name(module_event: str) -> str:
    """``jit_paged_step_mt(1234)`` -> ``paged_step_mt``: what the program
    registered itself as."""
    return _PROGRAM.match(module_event).group("name")


def group_of(row: Dict[str, str]) -> str:
    """A map row's line in ``by_module``: its path to ``GROUP_DEPTH`` with a
    layer's number starred (``layer_*/attention/q_a``), ``(no path)`` for
    none, and the phase where it is not ``forward``."""
    parts = [_INDEX.sub("*", p) for p in row["path"].split("/")[:GROUP_DEPTH]] if row["path"] else []
    name = "/".join(parts) or "(no path)"
    return name if row["phase"] == "forward" else f"{name} [{row['phase']}]"


def matches(row: Dict[str, str], args: dict) -> bool:
    """``path``, ``kernel``, ``phase``: lists of patterns, of which a row has
    to match one each (those given); ``not_path``: none."""
    for key in ("path", "kernel", "phase"):
        if key in args and not any(fnmatch.fnmatchcase(row[key], p) for p in args[key]):
            return False
    return not any(fnmatch.fnmatchcase(row["path"], p) for p in args.get("not_path", ()))


def join(trace, maps) -> dict:
    """``maps(name)`` gives a program's map or ``None``.  Returns ``{"programs":
    {name: {"runs", "module_ns", "ops": {instruction: ns over the runs},
    "map"}}, "unmatched_pct", "map_s"}`` over the first device: the runs of a
    program are its ``XLA Modules`` events that start inside the window, an
    operation belongs to the run that contains its start, and ``map_s`` is
    what asking for the maps took."""
    dev = next(iter(trace.devices.values()))
    w0, w1 = trace.window
    runs = sorted(dev["modules"], key=lambda e: e["start"])
    starts = [e["start"] for e in runs]
    programs: Dict[str, dict] = {}
    for e in runs:
        if w0 <= e["start"] < w1:
            p = programs.setdefault(program_name(e["name"]),
                                    {"runs": 0, "module_ns": 0.0, "ops": {}})
            p["runs"] += 1
            p["module_ns"] += e["dur"]
    t0 = time.perf_counter()
    for name, p in programs.items():
        p["map"] = maps(name)
    map_s = time.perf_counter() - t0
    owners = [programs.get(program_name(e["name"])) for e in runs]
    total = unmatched = 0.0
    for e in dev["ops"]:
        i = bisect.bisect_right(starts, e["start"]) - 1
        inside_run = i >= 0 and e["start"] < runs[i]["start"] + runs[i]["dur"]
        p = owners[i] if inside_run else None
        name = instruction(e["name"])
        known = p is not None and p["map"] is not None and name in p["map"]
        if w0 <= e["start"] < w1:
            total += e["self_ns"]
            unmatched += 0.0 if known else e["self_ns"]
        if known and w0 <= runs[i]["start"] < w1:
            p["ops"][name] = p["ops"].get(name, 0.0) + e["self_ns"]
    return {"programs": programs, "map_s": map_s,
            "unmatched_pct": 100.0 * unmatched / total if total else 0.0}


def by_module(programs: Dict[str, dict]) -> Dict[str, dict]:
    out = {}
    for name, p in programs.items():
        if p["map"] is None:
            continue
        groups: Dict[str, float] = {}
        for instr, ns in p["ops"].items():
            g = group_of(p["map"][instr])
            groups[g] = groups.get(g, 0.0) + ns / p["runs"] / 1e6
        out[name] = {"runs": p["runs"], "module_ms": p["module_ns"] / p["runs"] / 1e6,
                     "ops_ms": sum(groups.values()),
                     "groups": dict(sorted(groups.items(), key=lambda kv: -kv[1]))}
    return out


def joined(run) -> Optional[dict]:
    """The run's join, made at the first call and kept on ``run``; ``None``
    where the program publishes no maps or the trace holds no device."""
    if not hasattr(run, "ops_join"):
        run.ops_join = None
        trace = run.trace
        try:
            from fedml_tpu.obs import programs
        except ImportError:
            return None
        if trace is None or not trace.devices or trace.window_s <= 0:
            return None
        t0 = time.perf_counter()
        found = join(trace, programs.op_modules)
        if any(p["map"] is not None for p in found["programs"].values()):
            run.ops_join = found
            run.note(by_module=by_module(found["programs"]), unmatched_pct=found["unmatched_pct"],
                     map_s=found["map_s"], join_s=time.perf_counter() - t0 - found["map_s"])
    return run.ops_join


def matched_ms(found: dict, args: dict) -> Optional[float]:
    """Mean over the window's runs of the programs matching ``args["program"]``
    of the summed self time of the instructions that ``matches``, ms a run."""
    total, runs = 0.0, 0
    for name, p in found["programs"].items():
        if p["map"] is None or not fnmatch.fnmatchcase(name, args["program"]):
            continue
        runs += p["runs"]
        total += sum(ns for instr, ns in p["ops"].items() if matches(p["map"][instr], args))
    return total / runs / 1e6 if runs and total else None


def read(args: dict, run) -> Optional[float]:
    """``args["kind"]``:

    - ``module_ms``: :func:`matched_ms` of ``program`` (a pattern over the
      names programs register under), ``path`` / ``not_path`` / ``kernel`` /
      ``phase``;
    - ``kernel_roofline_pct``: ``roofline`` (a function of ``rooflines/``, given
      the configuration, the cell, the run's counters and the chip's peaks)
      returns the least seconds the chip could take for the matched
      instructions of one run, over that same summed time.
    """
    found = joined(run)
    if found is None:
        return None
    ms = matched_ms(found, args)
    kind = args["kind"]
    if kind == "module_ms":
        return ms
    if kind == "kernel_roofline_pct":
        least = xplane._roofline(args["roofline"])(run.cfg, run.cell, run.counters, run.peak)
        return 100.0 * least / (ms / 1e3) if ms and least else None
    raise ValueError(f"ops reader: unknown kind {kind!r}")
