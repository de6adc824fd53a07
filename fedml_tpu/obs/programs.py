"""Device time by module: which module each instruction of a compiled
hot-path program belongs to (docs/OBSERVABILITY.md, "Device time by module").

A device trace names every operation by its instruction in the optimized
module (``fusion.1225``, ``gated_matmul.13``) and says nothing of the model.
Only the process that compiled a program can say that ``fusion.1225`` is the
head and ``latent_attention.4`` layer 0's attention: the optimized HLO text
carries ``metadata={op_name=...}`` with the flax module path under the very
instruction names the trace uses.  This module keeps, for each program of
the hot path, what is needed to ask that of the compiler later, and reads
the answer:

- :func:`register` is called where a program is first launched (the engine's
  tick, chunk and slot-row programs, the bank's ``gather_row``, the round
  program).  It keeps a **weak** reference to the jitted callable and the
  ``jax.ShapeDtypeStruct`` tree of that launch's arguments: no device array,
  no engine, no parameters.  Nothing is lowered or compiled.
- :func:`op_modules` lowers the callable again from the kept abstract
  arguments (under its own compiler options; where a persistent compile cache
  is configured this is a load under the key of the program that ran),
  and maps every instruction of ``compiled.as_text()`` to ``{"path",
  "phase", "kernel", "op"}``.  It costs a trace of the function and a compile
  or a cache load: **seconds, on the caller's thread**.  Never call it on a
  thread that launches programs (the engine's loop, the round driver inside a
  timed round).

Nothing here touches the tracer's event stream: a map of some thousands of
rows is not an event.
"""

from __future__ import annotations

import re
import threading
import weakref
from typing import Dict, List, Optional

#: ``{program name: [_Program, ...]}``, oldest first; guarded by ``_LOCK``
_PROGRAMS: Dict[str, List["_Program"]] = {}
_LOCK = threading.Lock()


class _Program:
    """One registration: the callable by weak reference, its first launch's
    arguments as shapes."""

    __slots__ = ("name", "fn", "args", "kwargs")

    def __init__(self, name: str, jitted, args: tuple, kwargs: dict):
        self.name = name
        self.fn = weakref.ref(jitted, lambda _: _sweep(name))
        self.args, self.kwargs = _abstract((args, kwargs))


def _abstract(tree):
    """Arrays as ``ShapeDtypeStruct`` with their sharding; whatever else a
    launch was given (``None``, a Python scalar) as it is."""
    import jax

    def leaf(a):
        if not (hasattr(a, "shape") and hasattr(a, "dtype")):
            return a
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=getattr(a, "sharding", None))
    return jax.tree_util.tree_map(leaf, tree)


def _gone() -> None:
    """What an unregistered program's weak reference is replaced by."""


def _sweep(name: str) -> None:
    """Drop every registration under ``name`` whose callable is gone."""
    with _LOCK:
        rows = [p for p in _PROGRAMS.pop(name, []) if p.fn() is not None]
        if rows:
            _PROGRAMS[name] = rows


def register(name: str, jitted, args: tuple, kwargs: Optional[dict] = None) -> _Program:
    """Keep ``jitted`` (weakly) and the shapes of ``args`` under ``name``.
    Call it once a program, before the launch it describes (a donated
    argument still has its buffer then).  Returns the handle
    :func:`unregister` takes."""
    program = _Program(name, jitted, tuple(args), dict(kwargs or {}))
    with _LOCK:
        _PROGRAMS.setdefault(name, []).append(program)
    return program


def unregister(*programs: Optional[_Program]) -> None:
    """Forget registrations (an engine's ``stop()``); ``None`` is skipped."""
    for program in programs:
        if program is not None:
            program.fn = _gone
            _sweep(program.name)


def registered() -> List[str]:
    """Names with a registration whose callable is still alive."""
    with _LOCK:
        return sorted(name for name, rows in _PROGRAMS.items()
                      if any(p.fn() is not None for p in rows))


def op_modules(program) -> Optional[Dict[str, Dict[str, str]]]:
    """``{instruction name: {"path", "phase", "kernel", "op"}}`` of a program:
    a handle of :func:`register`, or a name (the newest live registration
    under it).  ``None`` if there is none or its callable is gone.  Lowers
    and compiles (module docstring: seconds; not on a launching thread)."""
    if isinstance(program, str):
        with _LOCK:
            rows = [p for p in _PROGRAMS.get(program, []) if p.fn() is not None]
        program = rows[-1] if rows else None
    fn = program.fn() if program is not None else None
    if fn is None:
        return None
    compiled = fn.lower(*program.args, **program.kwargs).compile()
    return parse_hlo(compiled.as_text())


# -- the optimized module's text ------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>[\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?P<root>ROOT )?%?(?P<name>[\w.\-]+) = .*? (?P<op>[a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="(?P<op_name>[^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_APPLIED = re.compile(r"\bto_apply=%?([\w.\-]+)")
#: name-stack segments that are control flow or a transform's own scope, not
#: a module
_CONTROL = re.compile(
    r"^(while|body|cond|closed_call|core_call|checkpoint|remat\d*|"
    r"rematted_computation|branch_\d+_fun|custom_jvp_call|custom_vjp_call\w*|"
    r"custom_lin|shard_map|pallas_call)$")
_WRAPPED = re.compile(r"^\w*\((?P<inner>.*)\)$")
_JITTED = re.compile(r"jit\((?P<function>[\w.<>]+)\)")


def _unwrap(segment: str) -> str:
    """``transpose(jvp(LlamaLM))`` -> ``LlamaLM``; ``vmap()`` -> ``""``; a
    segment wrapped by ``jit``, or a qualified name with ``<locals>`` in it,
    names a function, not a module: ``""``."""
    if "jit(" in segment or "<" in segment:
        return ""
    while True:
        m = _WRAPPED.match(segment)
        if m is None:
            return segment
        segment = m.group("inner")


def parse_op_name(op_name: str) -> Dict[str, str]:
    """``path`` and ``phase`` of one ``op_name``.

    ``path`` is the flax module path whatever wraps it: the scopes after the
    model's own (``LlamaLM`` alone, or inside ``jvp(...)``,
    ``transpose(jvp(...))``, ``vmap(...)``), without transforms (``jit(f)``,
    ``vmap()``), control flow (``while/body``, ``cond/branch_0_fun``,
    ``checkpoint``, ``closed_call``) and the primitive at the end.  A method
    other than ``__call__`` stays on its module (``layer_0/attention/
    attention._paged_attend`` reads ``layer_0/attention._paged_attend``), and
    so does any other named scope below a module (an einsum's own:
    ``layer_0/attention/bhsn,rhn->bhsr``; a kernel's).  Where no module is
    named, the outermost jitted function below the program's own, in
    brackets (``(streaming_xent)``: the head and the loss; ``(_gumbel)``: the
    sampling), and ``""`` where there is none either.

    ``phase``: ``recompute`` under ``rematted_computation``, else
    ``backward`` under a ``transpose(...)``, else ``forward``."""
    segments = op_name.split("/")
    phase = ("recompute" if "rematted_computation" in segments
             else "backward" if any(s.startswith("transpose(") for s in segments)
             else "forward")
    path: List[str] = []
    root = None
    for segment in segments[:-1]:               # the last is the primitive
        plain = _unwrap(segment)
        if not plain or plain == root or _CONTROL.match(plain):
            continue
        if root is None:                        # the model's own scope
            root = plain
        elif path and plain.startswith(path[-1] + "."):
            path[-1] = plain                    # a method of the module above
        else:
            path.append(plain)
    if root is None:
        functions = _JITTED.findall("/".join(segments[1:]))
        return {"path": f"({functions[0]})" if functions else "", "phase": phase}
    return {"path": "/".join(path), "phase": phase}


def _kernel(op: str, name: str, line: str, op_name: str) -> str:
    """A Pallas call's ``name=``: the scope just above ``pallas_call`` in its
    ``op_name``, which is also what the instruction is called."""
    if op != "custom-call" or "tpu_custom_call" not in line:
        return ""
    segments = op_name.split("/")
    if len(segments) > 1 and segments[-1] == "pallas_call":
        return segments[-2]
    return re.sub(r"(\.\d+|\.clone|\.remat\d*)+$", "", name)


def parse_hlo(text: str) -> Dict[str, Dict[str, str]]:
    """Every instruction of every computation that can show in a device trace
    (the entry, ``while`` bodies and conditions, branches, calls; not the
    insides of fusions or of reducers) -> ``{"path", "phase", "kernel",
    "op"}``.  A fusion takes the path of its root instruction; an instruction
    without ``op_name`` gets path ``""`` and stays in the map; a clone the
    compiler made to rematerialise (``.remat`` in its name) is phase
    ``recompute``."""
    computations: Dict[str, List[tuple]] = {}
    roots: Dict[str, str] = {}
    fused, applied = set(), set()
    rows: Optional[List[tuple]] = None
    at = ""
    for line in text.splitlines():
        if rows is None or not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m is not None:
                at = m.group("name")
                rows = computations.setdefault(at, [])
            elif line.startswith("}"):
                rows = None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        found = _OP_NAME.search(line)
        op_name = found.group("op_name") if found else ""
        name, op = m.group("name"), m.group("op")
        calls = _CALLS.search(line).group(1) if op == "fusion" else None
        rows.append((name, op, op_name, _kernel(op, name, line, op_name), calls))
        if m.group("root"):
            roots[at] = op_name
        applied.update(_APPLIED.findall(line))
        if calls is not None:
            fused.add(calls)
    out: Dict[str, Dict[str, str]] = {}
    for comp, instrs in computations.items():
        if comp in fused or comp in applied:
            continue
        for name, op, op_name, kernel, calls in instrs:
            row = parse_op_name(roots.get(calls) or op_name)
            if ".remat" in name:
                row["phase"] = "recompute"
            row.update(kernel=kernel, op=op)
            out[name] = row
    return out


def program_ops(handles: Dict[str, Optional[_Program]]) -> Dict[str, Optional[dict]]:
    """An owner's ``program_ops()``: ``{name: map}`` over its own handles (a
    program not launched yet has none, and no map)."""
    return {name: op_modules(handle) for name, handle in handles.items()}
