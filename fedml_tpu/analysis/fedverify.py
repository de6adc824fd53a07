"""fedverify — AOT lowering-level contract checker (ISSUE 10 tentpole).

fedlint (``fedlint.py``) checks what the *source* says and JaxRuntimeAudit
(``runtime.py``) checks what *happened at runtime*; nothing verified what
XLA actually *compiles*.  Two real failure classes motivated closing that
gap: GSPMD silently re-replicated the model-sharded server state on round
exit (the PR 6 bug — caught only because a TPU ran out of HBM), and the
ObsCarry ``collective_bytes`` model is hand-maintained with no check
against the collectives XLA really emits.

Because every registered program is a pure function of ``(state, cohort,
hparams)`` (the PR 7 round algebra, arXiv:2403.07128), the whole training
and serving surface AOT-lowers on abstract shapes — ``jit(...).lower()``
over ``ShapeDtypeStruct`` avals runs NO step and needs NO accelerator —
so the contracts that matter at pod scale (arXiv:2204.06514) verify
statically, in CI, on a CPU host.  Five contract families:

1. **sharding** — every ServerState / client-table leaf of a program's
   output must land on its declared resting placement
   (``MeshLayout.state_sharding``), with a dedicated *silent
   re-replication* detector (expected-sharded leaf compiled to a fully
   replicated output = the PR 6 bug class).
2. **collective census** — count/classify ``all-reduce`` /
   ``reduce-scatter`` / ``all-gather`` / ``all-to-all`` /
   ``collective-permute`` ops per mesh axis in the *compiled* module,
   total their payload bytes, and cross-check against the ObsCarry
   ``collective_bytes_{client,model}`` model — drift is a failure.
3. **donation** — every buffer the engine declares donated must appear in
   the module's ``input_output_alias`` map (a missed donation silently
   doubles peak HBM for that buffer).
4. **HBM fit** — reconcile the compiled module's per-chip argument+temp
   footprint with ``core/memory_estimate.py``: the estimator must upper
   bound the lowering, and a config the estimator admits under a budget
   must actually fit it.
5. **recompile surface** — fingerprint the staged-input signature set a
   config family presents to the jit cache and fail when it exceeds the
   declared budget (homo cohorts = 1 program; hetero = pow2 step
   classes).

Findings ride fedlint's machinery (:class:`~.fedlint.Finding`, severity,
JSON, exit codes) so one reporting plane serves both analyzers;
suppressions live in the verify manifest
(``tests/data/fedverify/contracts.json``) as ``{program, rule, reason}``
records, and the manifest pins the expected census per canonical config
so contract changes are reviewed diffs, not silent drift
(``tools/fedverify.py --update-manifest`` regenerates the measured
fields, preserving budgets/bands/suppressions).

Layering: the HLO/StableHLO parsing and check half of this module is pure
stdlib (unit-testable without jax); the program registry half imports the
engines lazily and lowers the exact jitted callables the drivers run,
exposed by the ``round_program`` / ``block_program`` /
``step_programs`` hooks (docs/FEDVERIFY.md, "How to add a program").
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from . import programs as registry
from .fedlint import (ERROR, WARNING, Finding, Rule, exit_code,  # noqa: F401
                      findings_to_json, render_findings)

# --------------------------------------------------------------------------
# rule registry (one reporting plane with fedlint)
# --------------------------------------------------------------------------

VERIFY_RULES: Dict[str, Rule] = {
    r.name: r
    for r in [
        Rule("sharding-contract", ERROR,
             "a program output leaf's compiled sharding differs from the "
             "layout's declared resting placement"),
        Rule("silent-rereplication", ERROR,
             "a leaf the layout declares SHARDED compiled to a fully "
             "replicated output — GSPMD silently forfeited the 1/(c*m) "
             "per-chip ownership on program exit (the PR 6 bug class)"),
        Rule("collective-census", ERROR,
             "the compiled module's collective ops (count/kind/axis or "
             "payload bytes) differ from the manifest-pinned census"),
        Rule("byte-model-drift", ERROR,
             "the ObsCarry collective_bytes model drifted outside the "
             "pinned band of the bytes the compiled collectives move"),
        Rule("donation-aliasing", ERROR,
             "a buffer declared donated is missing from the compiled "
             "module's input_output_alias map — peak HBM doubles for it"),
        Rule("hbm-fit", ERROR,
             "per-chip argument+temp footprint of the compiled module "
             "exceeds the memory estimator or the declared HBM budget "
             "the estimator admitted"),
        Rule("recompile-surface", ERROR,
             "a config family presents more distinct staged-input "
             "signatures to the jit cache than its declared budget"),
        Rule("manifest-missing", WARNING,
             "a registered program has no manifest entry pinning its "
             "census — run tools/fedverify.py --update-manifest and "
             "review the diff"),
    ]
}

#: mesh-axis buckets census ops classify into
AXES = ("client", "stage", "model", "world", "none")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

#: collective op kinds the census tracks (order = report order)
COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                    "all-to-all", "collective-permute")


@dataclasses.dataclass
class CollectiveOp:
    """One collective in a compiled module."""
    kind: str
    axis: str            # client | model | world | none
    nbytes: int          # payload bytes (operand for reductions/permutes,
    #                      result for gathers — the bytes one chip moves)
    result_shape: str
    operand_bytes: int
    result_bytes: int
    groups: Tuple[Tuple[int, ...], ...]


# --------------------------------------------------------------------------
# HLO text parsing (pure stdlib)
# --------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]+m[0-9]+(?:fn)?)?)"
                       r"\[([0-9,]*)\]")


def _shape_nbytes(segment: str) -> int:
    """Total bytes of every ``dtype[dims]`` shape token in ``segment``."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1
        for d in dims.split(","):
            if d.strip():
                elems *= int(d)
        total += elems * _DTYPE_BYTES[dtype]
    return total


_IOTA_RE = re.compile(r"\[([0-9,]+)\]<=\[([0-9,]+)\]"
                      r"(?:T\(([0-9,]+)\))?")


def _parse_replica_groups(text: str) -> List[List[int]]:
    """``replica_groups={{0,1},{2,3}}`` or the iota form
    ``[2,4]<=[4,2]T(1,0)`` -> explicit device-id groups."""
    text = text.strip()
    m = _IOTA_RE.match(text)
    if m:
        out_dims = [int(d) for d in m.group(1).split(",")]
        src_dims = [int(d) for d in m.group(2).split(",")]
        n = 1
        for d in src_dims:
            n *= d
        ids = list(range(n))
        if m.group(3):
            perm = [int(p) for p in m.group(3).split(",")]
            # reshape ids to src_dims, transpose by perm, flatten
            strides = [0] * len(src_dims)
            acc = 1
            for i in range(len(src_dims) - 1, -1, -1):
                strides[i] = acc
                acc *= src_dims[i]
            tdims = [src_dims[p] for p in perm]
            tstrides = [strides[p] for p in perm]
            flat = []

            def rec(depth, off):
                if depth == len(tdims):
                    flat.append(off)
                    return
                for i in range(tdims[depth]):
                    rec(depth + 1, off + i * tstrides[depth])

            rec(0, 0)
            ids = flat
        group = out_dims[-1] if out_dims else n
        return [ids[i:i + group] for i in range(0, len(ids), group)]
    groups: List[List[int]] = []
    for g in re.findall(r"\{([0-9,\s]+)\}", text):
        groups.append([int(d) for d in g.split(",") if d.strip()])
    return groups


def classify_groups(groups: Sequence[Sequence[int]],
                    mesh_shape: Tuple[int, ...]) -> str:
    """Which mesh axis a collective's device groups span.

    Device ids follow the canonical mesh layout (``core.mesh.make_mesh``)
    with data/seq pinned to 1: on the 2-D ``(c, m)`` layout
    ``id = client_coord * m + model_coord``; on the 3-D pipeline layout
    ``(c, s, m)`` it is ``(client_coord * s + stage_coord) * m +
    model_coord`` (docs/PIPELINE.md) — so a stage-ring
    ``collective-permute``'s pairs vary only the middle coordinate."""
    dims = tuple(int(d) for d in mesh_shape)
    names = (("client", "model") if len(dims) == 2
             else ("client", "stage", "model"))
    axes: Set[str] = set()
    for g in groups:
        if len(g) <= 1:
            continue
        varying: Set[str] = set()
        inner = 1
        for i in range(len(dims) - 1, -1, -1):
            coords = {(d // inner) % dims[i] for d in g}
            if len(coords) > 1:
                varying.add(names[i])
            inner *= dims[i]
        if len(varying) > 1:
            axes.add("world")
        elif varying:
            axes.add(varying.pop())
    if not axes:
        return "none"
    if len(axes) == 1:
        return axes.pop()
    return "world"


_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<result>[^=]*?)\s*"
    r"(?P<kind>all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def parse_collectives(hlo: str,
                      mesh_shape: Tuple[int, int]) -> List[CollectiveOp]:
    """Census of every collective op in a compiled (post-SPMD) HLO
    module.  Payload-byte convention: reductions/permutes/all-to-all
    count operand bytes (what enters the wire), gathers count result
    bytes (what one chip assembles) — consistent with the ObsCarry model
    (docs/COLLECTIVE_PRECISION.md)."""
    ops: List[CollectiveOp] = []
    for line in hlo.splitlines():
        # tuple shapes carry ``/*index=5*/`` markers whose ``=`` would cut
        # the result segment short
        line = re.sub(r"/\*.*?\*/", "", line)
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        kind = m.group("kind")
        result_seg = m.group("result")
        operand_seg = line[m.end():]
        # strip trailing attribute clauses from the operand segment so
        # attribute shapes (none today) can't pollute the byte count
        operand_seg = operand_seg.split("), ")[0]
        rg = re.search(r"replica_groups=("
                       r"\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?"
                       r"|\{[0-9,{}\s]*\})", line)
        if rg:
            groups = _parse_replica_groups(rg.group(1))
        else:
            pairs = re.search(r"source_target_pairs=\{(.*?)\}\}", line)
            if pairs:
                # permute pairs: classify by the coordinate that moves
                ids = re.findall(r"\{(\d+),(\d+)\}", pairs.group(0))
                groups = [[int(a), int(b)] for a, b in ids]
            else:
                groups = []
        axis = classify_groups(groups, mesh_shape)
        result_bytes = _shape_nbytes(result_seg)
        if _SHAPE_RE.search(operand_seg):
            operand_bytes = _shape_nbytes(operand_seg)
        else:
            # the installed XLA prints operands by name only: recover the
            # operand bytes from the result (a scatter's operand is the
            # result times the group size, a gather's the result over it;
            # every other collective preserves the shape)
            gsize = max((len(g) for g in groups), default=1)
            operand_bytes = {"reduce-scatter": result_bytes * gsize,
                             "all-gather": result_bytes // gsize}.get(
                                 kind, result_bytes)
        nbytes = result_bytes if kind == "all-gather" else operand_bytes
        ops.append(CollectiveOp(
            kind=kind, axis=axis, nbytes=nbytes,
            result_shape=result_seg.strip(),
            operand_bytes=operand_bytes, result_bytes=result_bytes,
            groups=tuple(tuple(g) for g in groups)))
    return ops


def parse_io_aliases(hlo: str) -> Set[int]:
    """Flat parameter indices of the module's ``input_output_alias`` map
    (the donations XLA actually honored).  The map nests braces
    (``{1}: (1, {}, may-alias)``), so scan balanced rather than regex to
    the first ``}``."""
    start = hlo.find("input_output_alias={")
    if start < 0:
        return set()
    i = hlo.index("{", start)
    depth, j = 0, i
    for j in range(i, min(len(hlo), i + 100_000)):
        if hlo[j] == "{":
            depth += 1
        elif hlo[j] == "}":
            depth -= 1
            if depth == 0:
                break
    body = hlo[i:j + 1]
    return {int(p) for p in re.findall(r":\s*\((\d+)", body)}


def parse_num_partitions(hlo: str) -> int:
    m = re.search(r"num_partitions=(\d+)", hlo)
    return int(m.group(1)) if m else 1


_MLIR_DTYPES = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int64": "i64", "int32": "i32", "int16": "i16",
    "int8": "i8", "uint64": "ui64", "uint32": "ui32", "uint16": "ui16",
    "uint8": "ui8", "bool": "i1",
}


def parse_stablehlo_args(stablehlo: str) -> List[Tuple[Tuple[int, ...],
                                                       str, bool]]:
    """``(shape, mlir dtype, is_buffer_donor)`` per ``@main`` argument of
    a lowered module.  Argument numbering here matches the compiled
    module's parameter numbering (jit prunes dead args BEFORE emitting
    StableHLO, and the SPMD partitioner preserves parameter order)."""
    start = stablehlo.find("@main")
    if start < 0:
        return []
    sig = stablehlo[start:]
    cut = sig.find("->")
    sig = sig[:cut if cut > 0 else len(sig)]
    out = []
    for m in re.finditer(
            r"%arg\d+:\s*tensor<([^>]*)>\s*(\{[^}]*\})?", sig):
        parts = m.group(1).split("x")
        dtype = parts[-1]
        dims = tuple(int(d) for d in parts[:-1])
        attrs = m.group(2) or ""
        donor = ("jax.buffer_donor" in attrs
                 or "tf.aliasing_output" in attrs)
        out.append((dims, dtype, donor))
    return out


def align_donated_args(leaves: Sequence[Tuple[Tuple[int, ...], str]],
                       donated_flat: Set[int],
                       module_args: Sequence[Tuple[Tuple[int, ...], str,
                                                   bool]]
                       ) -> Tuple[Set[int], Set[int]]:
    """Map engine-declared donated flat leaves onto the lowered module's
    (pruned) argument numbering.

    jit silently drops arguments nothing consumes (e.g. the RNG key
    stack of a dropout-free fp32 config), renumbering every later
    parameter — so donated indices must be re-derived against the module
    by aligning the flat (shape, dtype) sequence greedily (order is
    preserved; a leaf that doesn't match the next kept argument was
    pruned).  Returns ``(kept_donated, undonated)``: module arg indices
    of the donated leaves that survived, and the subset of those the
    module does NOT mark ``jax.buffer_donor`` (a donation lost at trace
    level)."""
    kept: Set[int] = set()
    undonated: Set[int] = set()
    j = 0
    for i, (shape, dtype) in enumerate(leaves):
        if j >= len(module_args):
            break
        mshape, mdtype, donor = module_args[j]
        if mshape == tuple(shape) and mdtype == dtype:
            if i in donated_flat:
                kept.add(j)
                if not donor:
                    undonated.add(j)
            j += 1
        # else: leaf i was pruned from the module; stay on arg j
    return kept, undonated


def leaf_sig(leaf) -> Tuple[Tuple[int, ...], str]:
    """(shape, mlir dtype) of one abstract arg leaf."""
    import numpy as np
    name = np.dtype(leaf.dtype).name
    return tuple(leaf.shape), _MLIR_DTYPES.get(name, name)


def count_stablehlo_collectives(stablehlo: str) -> Dict[str, int]:
    """Pre-partitioning view: explicit ``stablehlo.*`` collective ops
    (the shard_map-manual collectives the *program* asked for, before
    GSPMD adds the ones sharding propagation needs)."""
    out = {}
    for op in ("all_reduce", "reduce_scatter", "all_gather", "all_to_all",
               "collective_permute"):
        n = len(re.findall(r"stablehlo\." + op + r"\b", stablehlo))
        if n:
            out[op.replace("_", "-")] = n
    return out


# --------------------------------------------------------------------------
# program report + checks (pure once the report exists)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramReport:
    """Everything the contract checks need about one lowered program."""
    name: str
    mesh_shape: Tuple[int, int]
    num_partitions: int
    collectives: List[CollectiveOp]
    requested_collectives: Dict[str, int]     # stablehlo (pre-SPMD) view
    donated_params: Set[int]                  # declared (module arg idx)
    undonated_params: Set[int]                # declared but not donor-marked
    aliased_params: Set[int]                  # honored by the module
    #: [(leaf path, expected spec, actual spec)] where expected != actual
    sharding_violations: List[Tuple[str, str, str]]
    #: leaf paths expected sharded that compiled fully replicated
    rereplicated: List[str]
    n_sharding_leaves: int                    # leaves actually compared
    modeled_bytes: Dict[str, float]           # ObsCarry model, per axis
    memory: Dict[str, float]                  # per-chip module footprint
    estimate_bytes: float                     # memory_estimate upper bound
    signatures: List[str]
    signature_budget: int

    # -- census views ------------------------------------------------------
    def collective_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.collectives:
            key = f"{op.kind}.{op.axis}"
            out[key] = out.get(key, 0) + 1
        return dict(sorted(out.items()))

    def census_bytes(self) -> Dict[str, float]:
        out = {a: 0.0 for a in AXES}
        for op in self.collectives:
            out[op.axis] += float(op.nbytes)
        return {a: b for a, b in out.items() if b}

    def per_chip_total(self) -> float:
        m = self.memory
        return (m.get("argument", 0.0) + m.get("temp", 0.0)
                + m.get("output", 0.0) - m.get("alias", 0.0))

    def to_manifest_entry(self) -> Dict[str, Any]:
        """Measured census fields of a manifest entry (budgets/bands are
        policy, added/kept by the manifest writer)."""
        return {
            "mesh_shape": list(self.mesh_shape),
            "num_partitions": self.num_partitions,
            "collectives": self.collective_counts(),
            "requested_collectives": dict(sorted(
                self.requested_collectives.items())),
            "census_bytes": {k: round(v) for k, v in
                             self.census_bytes().items()},
            "modeled_bytes": {k: round(v) for k, v in
                              self.modeled_bytes.items() if v},
            "donated": sorted(self.donated_params),
            "hbm": {k: round(v) for k, v in self.memory.items()},
            "per_chip_total": round(self.per_chip_total()),
            "estimate_bytes": round(self.estimate_bytes),
            "distinct_signatures": len(set(self.signatures)),
        }


#: default policy fields stamped into fresh manifest entries
DEFAULT_BYTES_TOL = 0.10
#: census/model ratio band: the ObsCarry model prices the intended hot-
#: path wire traffic; GSPMD's fp32 staging (flat-view gathers) legally
#: rides on top, so the band admits up to 4x before calling drift
DEFAULT_RATIO_BAND = (0.25, 4.0)
DEFAULT_HBM_BUDGET = 256 * 1024 * 1024
#: census bytes on an axis the model prices at zero below this are noise
#: (scalar psums, key permutes), not drift
DRIFT_FLOOR_BYTES = 4096


def _find(rule: str, program: str, msg: str) -> Finding:
    return Finding(rule=rule, severity=VERIFY_RULES[rule].severity,
                   path=f"fedverify:{program}", line=0, col=0, message=msg)


def run_checks(report: ProgramReport, entry: Optional[Dict[str, Any]],
               suppressions: Iterable[Dict[str, str]] = ()) -> List[Finding]:
    """The five contract families over one program report + its manifest
    entry.  Returns findings with manifest suppressions applied."""
    p = report.name
    out: List[Finding] = []

    # 1. sharding contracts --------------------------------------------------
    for path, exp, act in report.sharding_violations:
        out.append(_find("sharding-contract", p,
                         f"output leaf {path}: compiled sharding {act} != "
                         f"declared resting placement {exp}"))
    for path in report.rereplicated:
        out.append(_find(
            "silent-rereplication", p,
            f"output leaf {path} is declared SHARDED but compiled fully "
            f"replicated — each chip now holds the whole buffer "
            f"(docs/MESH_2D.md resting-placement contract)"))

    # 2. collective census ---------------------------------------------------
    if entry is None:
        out.append(_find("manifest-missing", p,
                         "no contracts.json entry pins this program's "
                         "census"))
    else:
        counts = report.collective_counts()
        want = dict(entry.get("collectives", {}))
        if counts != want:
            diff = []
            for k in sorted(set(counts) | set(want)):
                a, b = counts.get(k, 0), want.get(k, 0)
                if a != b:
                    diff.append(f"{k}: compiled {a} != pinned {b}")
            out.append(_find("collective-census", p,
                             "collective census drifted from the "
                             "manifest: " + "; ".join(diff)))
        tol = float(entry.get("bytes_tolerance", DEFAULT_BYTES_TOL))
        got_b = report.census_bytes()
        want_b = {k: float(v)
                  for k, v in entry.get("census_bytes", {}).items()}
        for axis in sorted(set(got_b) | set(want_b)):
            a, b = got_b.get(axis, 0.0), want_b.get(axis, 0.0)
            if b == 0.0 and a > DRIFT_FLOOR_BYTES:
                out.append(_find("collective-census", p,
                                 f"{axis}-axis collectives move {a:.0f} "
                                 f"bytes; manifest pins none"))
            elif b > 0.0 and abs(a - b) > tol * b:
                out.append(_find(
                    "collective-census", p,
                    f"{axis}-axis collective bytes {a:.0f} drifted past "
                    f"±{tol:.0%} of the pinned {b:.0f}"))

        # 2b. ObsCarry byte-model cross-check ------------------------------
        band = entry.get("model_ratio_band", list(DEFAULT_RATIO_BAND))
        lo, hi = float(band[0]), float(band[1])
        for axis in ("client", "stage", "model"):
            modeled = float(report.modeled_bytes.get(axis, 0.0))
            actual = got_b.get(axis, 0.0)
            if modeled <= 0.0:
                if actual > DRIFT_FLOOR_BYTES:
                    out.append(_find(
                        "byte-model-drift", p,
                        f"ObsCarry models zero {axis}-axis bytes but the "
                        f"compiled collectives move {actual:.0f}"))
                continue
            ratio = actual / modeled
            if not (lo <= ratio <= hi):
                out.append(_find(
                    "byte-model-drift", p,
                    f"compiled {axis}-axis bytes {actual:.0f} are "
                    f"{ratio:.2f}x the ObsCarry model's {modeled:.0f} — "
                    f"outside the pinned band [{lo}, {hi}] "
                    f"(docs/COLLECTIVE_PRECISION.md wire model)"))

    # 3. donation ------------------------------------------------------------
    undonated = sorted(report.undonated_params)
    if undonated:
        out.append(_find(
            "donation-aliasing", p,
            f"input leaves {undonated} the engine declares donated carry "
            f"no jax.buffer_donor mark in the lowered module — the "
            f"donation was lost at the jit boundary (dropped donation)"))
    missing = sorted(report.donated_params - report.undonated_params
                     - report.aliased_params)
    if missing:
        out.append(_find(
            "donation-aliasing", p,
            f"declared-donated input leaves {missing} are absent from "
            f"the compiled module's input_output_alias map — XLA will "
            f"keep both copies live (dropped donation)"))

    # 4. HBM fit -------------------------------------------------------------
    measured = report.per_chip_total()
    budget = float((entry or {}).get("hbm_budget_bytes",
                                     DEFAULT_HBM_BUDGET))
    est = float(report.estimate_bytes)
    if est > 0.0 and measured > est:
        out.append(_find(
            "hbm-fit", p,
            f"per-chip lowered footprint {measured:.0f} B exceeds the "
            f"memory estimator's {est:.0f} B — the estimator no longer "
            f"upper-bounds the lowering, so its 'fits' verdicts are "
            f"unsound (core/memory_estimate.py)"))
    if est <= budget < measured:
        out.append(_find(
            "hbm-fit", p,
            f"estimator admits this config under the "
            f"{budget:.0f} B budget ({est:.0f} B) but the compiled "
            f"module needs {measured:.0f} B/chip — it would OOM on the "
            f"hardware the estimate approved"))

    # 5. recompile surface ---------------------------------------------------
    distinct = len(set(report.signatures))
    budget_n = int((entry or {}).get("signature_budget",
                                     report.signature_budget))
    if distinct > budget_n:
        out.append(_find(
            "recompile-surface", p,
            f"config family presents {distinct} distinct staged-input "
            f"signatures to the jit cache (budget {budget_n}) — every "
            f"extra signature is a full recompile at run time"))

    # manifest suppressions ---------------------------------------------------
    for f in out:
        for s in suppressions:
            if s.get("rule") == f.rule and \
                    s.get("program") in (p, "*"):
                f.suppressed = True
                reason = s.get("reason", "")
                if reason:
                    f.message += f" [suppressed: {reason}]"
    return out


# --------------------------------------------------------------------------
# manifest
# --------------------------------------------------------------------------

def default_manifest_path() -> str:
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo, "tests", "data", "fedverify",
                        "contracts.json")


def load_manifest(path: Optional[str] = None) -> Dict[str, Any]:
    path = path or default_manifest_path()
    if not os.path.exists(path):
        return {"version": 1, "programs": {}, "suppressions": []}
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def update_manifest(reports: Sequence[ProgramReport],
                    path: Optional[str] = None) -> Dict[str, Any]:
    """Refresh the measured census fields, preserving policy fields
    (budgets, tolerance bands) and suppressions — the diff is the review
    surface."""
    path = path or default_manifest_path()
    manifest = load_manifest(path)
    progs = manifest.setdefault("programs", {})
    for rep in reports:
        old = progs.get(rep.name, {})
        entry = rep.to_manifest_entry()
        entry["bytes_tolerance"] = old.get("bytes_tolerance",
                                           DEFAULT_BYTES_TOL)
        entry["model_ratio_band"] = old.get("model_ratio_band",
                                            list(DEFAULT_RATIO_BAND))
        entry["hbm_budget_bytes"] = old.get("hbm_budget_bytes",
                                            DEFAULT_HBM_BUDGET)
        entry["signature_budget"] = old.get("signature_budget",
                                            rep.signature_budget)
        progs[rep.name] = entry
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(manifest, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return manifest


# --------------------------------------------------------------------------
# lowering (jax from here down; imported lazily so the parsing half stays
# stdlib-importable)
# --------------------------------------------------------------------------

def _abstract(tree):
    """Concrete staged args -> ShapeDtypeStruct avals carrying the staged
    shardings, so ``.lower`` sees exactly what the driver's call would
    present — without touching (or needing) the data."""
    import jax
    from jax.sharding import NamedSharding

    def leaf(l):
        sh = getattr(l, "sharding", None)
        if not isinstance(sh, NamedSharding):
            sh = None
        return jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh)

    return jax.tree_util.tree_map(leaf, tree)


def donated_leaf_indices(args: Sequence[Any],
                         donate_argnums: Sequence[int]) -> Set[int]:
    """Flat module-parameter indices of the donated positional args (jit
    flattens args in order; None subtrees contribute no leaves)."""
    import jax
    idx, out = 0, set()
    donate = set(donate_argnums)
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in donate:
            out.update(range(idx, idx + n))
        idx += n
    return out


def _leaf_path_items(tree) -> List[Tuple[str, Any]]:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def compare_shardings(actual_tree, expected_tree, out_struct_tree,
                      prefix: str = ""):
    """(violations, rereplicated, n_compared) between a compiled output
    subtree's shardings and the layout's declared resting placement."""
    import jax
    violations: List[Tuple[str, str, str]] = []
    rerepl: List[str] = []
    act = _leaf_path_items(actual_tree)
    exp = _leaf_path_items(expected_tree)
    structs = _leaf_path_items(out_struct_tree)
    if len(act) != len(exp) or len(act) != len(structs):
        violations.append((prefix or "<tree>",
                           f"{len(exp)} leaves", f"{len(act)} leaves"))
        return violations, rerepl, 0
    n = 0
    for (path, a), (_, e), (_, st) in zip(act, exp, structs):
        if e is None:
            continue
        n += 1
        shape = tuple(getattr(st, "shape", ()))
        try:
            same = a.is_equivalent_to(e, len(shape))
        except Exception:
            same = str(a) == str(e)
        if same:
            continue
        name = prefix + path
        # the PR 6 class: the compiled output spreads the leaf over FEWER
        # devices than declared — some mesh factor (e.g. ``model`` under
        # a partial-auto shard_map) silently re-replicated, so each chip
        # holds more of the buffer than the layout budgeted
        if _shard_count(a, shape) < _shard_count(e, shape):
            rerepl.append(name)
        else:
            violations.append((name, _spec_str(e), _spec_str(a)))
    return violations, rerepl, n


def _shard_count(sharding, shape) -> int:
    """How many distinct shards a sharding splits ``shape`` into (1 =
    fully replicated)."""
    try:
        local = sharding.shard_shape(tuple(shape))
    except Exception:
        return 1
    total = math.prod(shape) or 1
    per = math.prod(local) or 1
    return max(1, total // per)


def _spec_str(sharding) -> str:
    spec = getattr(sharding, "spec", None)
    return str(spec) if spec is not None else str(sharding)


def lower_program(name: str, jit_fn, args: Sequence[Any],
                  donate_argnums: Sequence[int],
                  mesh_shape: Tuple[int, int] = (1, 1),
                  expected_out: Optional[Dict[int, Any]] = None,
                  modeled_bytes: Optional[Dict[str, float]] = None,
                  estimate_bytes: float = 0.0,
                  signatures: Sequence[str] = ("static",),
                  signature_budget: int = 1) -> ProgramReport:
    """AOT-lower ``jit_fn`` on ``args``' abstract avals, compile on the
    host platform, and assemble the :class:`ProgramReport` the contract
    checks consume.  ``expected_out`` maps output tuple indices to
    expected-sharding pytrees (``None`` leaves are unchecked)."""
    import jax

    absargs = _abstract(tuple(args))
    lowered = jit_fn.lower(*absargs)
    stablehlo = lowered.as_text()
    compiled = lowered.compile()
    hlo = compiled.as_text()

    num_partitions = parse_num_partitions(hlo)
    collectives = parse_collectives(hlo, mesh_shape)
    aliased = parse_io_aliases(hlo)
    flat_sigs = [leaf_sig(l)
                 for l in jax.tree_util.tree_leaves(absargs)]
    donated, undonated = align_donated_args(
        flat_sigs, donated_leaf_indices(args, donate_argnums),
        parse_stablehlo_args(stablehlo))

    violations: List[Tuple[str, str, str]] = []
    rerepl: List[str] = []
    n_cmp = 0
    if expected_out:
        out_struct = jax.eval_shape(jit_fn, *absargs)
        out_shardings = compiled.output_shardings
        for idx, expected in expected_out.items():
            if expected is None:
                continue
            v, r, n = compare_shardings(out_shardings[idx], expected,
                                        out_struct[idx],
                                        prefix=f"out[{idx}]")
            violations += v
            rerepl += r
            n_cmp += n

    mem: Dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        for key, attr in (("argument", "argument_size_in_bytes"),
                          ("output", "output_size_in_bytes"),
                          ("temp", "temp_size_in_bytes"),
                          ("alias", "alias_size_in_bytes")):
            mem[key] = float(getattr(ma, attr, 0) or 0)

    return ProgramReport(
        name=name, mesh_shape=tuple(mesh_shape),
        num_partitions=num_partitions, collectives=collectives,
        requested_collectives=count_stablehlo_collectives(stablehlo),
        donated_params=donated, undonated_params=undonated,
        aliased_params=aliased,
        sharding_violations=violations, rereplicated=rerepl,
        n_sharding_leaves=n_cmp,
        modeled_bytes=dict(modeled_bytes or {}),
        memory=mem, estimate_bytes=float(estimate_bytes),
        signatures=list(signatures),
        signature_budget=int(signature_budget))


# --------------------------------------------------------------------------
# canonical program registry
# --------------------------------------------------------------------------

#: rounds enumerated when fingerprinting a program's recompile surface
SIGNATURE_ROUNDS = 4


def _canonical_args(**over):
    """One tiny, fast, deterministic config family every canonical
    program derives from (mirrors tests/test_mesh.py::args_for)."""
    import fedml_tpu
    from ..arguments import load_arguments
    args = load_arguments()
    args.update(
        dataset="synthetic", num_classes=10, input_shape=(28, 28, 1),
        train_size=256, test_size=64, model="lr",
        client_num_in_total=16, client_num_per_round=8, comm_round=8,
        epochs=1, batch_size=16, learning_rate=0.1, random_seed=7,
        frequency_of_the_test=100,
        # homo partition => every cohort pads to ONE pow2 step class, so
        # the canonical recompile budget is exactly 1 program (the hetero
        # pow2-class budget is exercised by the mutation tests)
        partition_method="homo",
    )
    args.update(**over)
    return fedml_tpu.init(args)


def _make_api(args):
    from .. import data as data_mod, device as device_mod, model as model_mod
    dev = device_mod.get_device(args)
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    if getattr(args, "backend", "sp") == "mesh":
        from ..simulation.mesh.engine import MeshFedAvgAPI
        return MeshFedAvgAPI(args, dev, dataset, model)
    from ..simulation.sp.fedavg_api import FedAvgAPI
    return FedAvgAPI(args, dev, dataset, model)


def _data_plane_bytes(args_tuple, state) -> float:
    """Per-chip bytes of the non-state inputs of a staged round call —
    exact, from each leaf's shape/sharding (the lowering's data plane the
    state estimator doesn't price)."""
    import jax
    import numpy as np

    def per_chip(leaf) -> float:
        shape = tuple(leaf.shape)
        nbytes = float(np.dtype(leaf.dtype).itemsize) * float(
            math.prod(shape) or 1)
        sh = getattr(leaf, "sharding", None)
        if sh is None or not shape:
            return nbytes
        try:
            local = sh.shard_shape(shape)
        except Exception:
            return nbytes
        frac = math.prod(local) / max(1, math.prod(shape))
        return nbytes * frac

    state_ids = {id(l) for l in jax.tree_util.tree_leaves(state)}
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tuple(args_tuple)):
        if id(leaf) in state_ids:
            continue
        total += per_chip(leaf)
    return total


def _stage_fraction(api) -> float:
    """Fraction of the params living in the staged leaves (the layer-
    stacked chunks that shard over ``stage`` — docs/PIPELINE.md)."""
    import jax
    params = api.state.global_params
    staged = set(api.trainer.pipe.stage_leaves)
    total = sta = 0
    for name, sub in params.items():
        n = sum(int(l.size) for l in jax.tree_util.tree_leaves(sub))
        total += n
        if name in staged:
            sta += n
    return sta / max(1, total)


def _mesh_round_estimate(api, args_tuple, members: int = 1,
                         steps: int = 1, rounds_fused: int = 1) -> float:
    """Upper-bound per-chip footprint from core/memory_estimate.py plus
    the exact data plane of this staged call."""
    from ..core import tree as tree_util
    from ..core.memory_estimate import (MeshStateLayout,
                                        estimate_round_footprint)
    c = int(getattr(api, "n_shards", 1))
    m = int(getattr(api, "n_model_shards", 1))
    s = int(getattr(api, "n_stage_shards", 1))
    n_params = tree_util.num_params(
        api.state.global_params) // max(1, members)
    shape = (c, s, m) if s > 1 else (c, m)
    lo = MeshStateLayout(
        n_params=n_params, mesh_shape=shape,
        clients_per_round=api.clients_per_round,
        algorithm=api.server_opt.algorithm,
        collective_precision=api.collective_precision,
        stage_fraction=_stage_fraction(api) if s > 1 else 1.0)
    cohort_bytes = _cohort_work_bytes(api, steps)
    data_bytes = _data_plane_bytes(args_tuple, api.state)
    return estimate_round_footprint(
        lo, data_bytes=data_bytes, cohort_bytes=cohort_bytes,
        members=members, rounds_fused=rounds_fused)["total"]


def _cohort_work_bytes(api, steps: int) -> float:
    """Gathered cohort tensors per chip (x + y at f32) at the staged
    pow2-padded step count — the term the round's temps scale with."""
    clients_local = -(-api.clients_per_round
                      // int(getattr(api, "n_shards", 1)))
    shape = tuple(api.dataset.train_x.shape[1:])
    feat = math.prod(shape) or 1
    return float(clients_local * max(1, steps) * api.batch_size
                 * (feat + 1) * 4)


def _modeled_round_bytes(api, steps: int = 1) -> Dict[str, float]:
    """The ObsCarry collective_bytes model for one mesh round — computed
    exactly the way ``mesh/engine.py::_bytes_model`` does."""
    from ..core import tree as tree_util
    from ..simulation.mesh import collectives as coll
    scatter = api.update_sharding == "scatter"
    if scatter:
        n_flat = api.layout.flat_spec_of(
            api.state.global_params).padded_size
    else:
        n_flat = tree_util.num_params(api.state.global_params)
    mode = "scatter" if scatter else "replicated"
    m = api.n_model_shards
    s = int(getattr(api, "n_stage_shards", 1))
    n_payload = n_flat if scatter else -(-n_flat // (m * s))
    cbytes = coll.client_axis_bytes(n_payload, api.n_shards,
                                    api.collective_precision,
                                    api.quant_block, mode)
    mbytes = coll.model_axis_bytes(n_flat, m, mode=mode)
    out = {"client": float(cbytes), "model": float(mbytes)}
    if s > 1:
        tr = api.trainer
        out["stage"] = float(coll.stage_axis_bytes(
            n_flat, s, mode=mode, hidden=tr.hidden,
            microbatch=api.batch_size // tr.n_micro,
            n_micro=tr.n_micro, steps=steps))
    return out


def _build_sp(name: str, **over) -> ProgramReport:
    api = _make_api(_canonical_args(backend="sp", **over))
    progs = {kind: (fn, args, donate)
             for kind, fn, args, donate in api.lowerable_programs()}
    fn, args, donate = progs["round"]
    sigs = [api.round_signature(r) for r in range(SIGNATURE_ROUNDS)]
    members = api.population.size if api.population else 1
    est = _mesh_round_estimate(api, args, members=members,
                               steps=int(args[1].shape[1]))
    return lower_program(name, fn, args, donate, mesh_shape=(1, 1),
                         estimate_bytes=est, signatures=sigs)


@registry.register("sp_round", "sp", "round", quick=True)
def build_sp_round() -> ProgramReport:
    """Single-process round: the reference program every mesh layout must
    match (vmap clients, gather cohort)."""
    return _build_sp("sp_round")


@registry.register("population_p4", "sp", "round")
def build_population_p4() -> ProgramReport:
    """P=4 experiment population vmapped over the sp round — one
    dispatch, member-stacked state (docs/PRIMITIVES.md)."""
    return _build_sp("population_p4", population=4)


def _make_async_api():
    from ..simulation.async_engine import FedBuffAPI
    args = _canonical_args(backend="sp", federated_optimizer="fedbuff")
    from .. import data as data_mod, device as device_mod, model as model_mod
    dev = device_mod.get_device(args)
    dataset, out_dim = data_mod.load(args)
    model = model_mod.create(args, out_dim)
    return FedBuffAPI(args, dev, dataset, model)


@registry.register("async_dispatch", "async", "dispatch")
def build_async_dispatch() -> ProgramReport:
    """The buffered-async engine's generation dispatch (docs/ASYNC.md):
    client phase + per-client unreduced aggregate rows, staged exactly
    like the sync round."""
    api = _make_async_api()
    fn, args, donate = api.dispatch_program(0)
    sigs = [api.dispatch_signature(g) for g in range(SIGNATURE_ROUNDS)]
    est = _mesh_round_estimate(api, args, steps=int(args[1].shape[1]))
    return lower_program("async_dispatch", fn, args, donate,
                         mesh_shape=(1, 1), estimate_bytes=est,
                         signatures=sigs)


@registry.register("async_buffer_apply", "async", "buffer")
def build_async_apply() -> ProgramReport:
    """The buffered-async engine's buffer apply: finish the size-K row
    buffer (occupancy/staleness as traced data) + server transition,
    with the buffer donated for the in-place reset."""
    api = _make_async_api()
    fn, args, donate = api.buffer_program()
    est = _mesh_round_estimate(api, args, steps=1)
    return lower_program("async_buffer_apply", fn, args, donate,
                         mesh_shape=(1, 1), estimate_bytes=est)


def _build_mesh(name: str, mesh_shape: str, update_sharding: str,
                alg: str = "FedAvg", block: int = 1,
                precision: str = "fp32", **over) -> ProgramReport:
    api = _make_api(_canonical_args(
        backend="mesh", mesh_shape=mesh_shape,
        update_sharding=update_sharding, federated_optimizer=alg,
        collective_precision=precision, round_block=block, **over))
    scatter = api.update_sharding == "scatter"
    quantized = api.collective_precision != "fp32"
    progs = {kind: (fn, args, donate)
             for kind, fn, args, donate in api.lowerable_programs()}
    expected = {0: api.layout.state_sharding(api.state, scatter,
                                             quantized)}
    if block > 1:
        fn, args, donate = progs["block"]
        if api.client_table is not None:
            expected[2] = api.layout.table_sharding(api.client_table)
        sigs = [api.block_signature(s)
                for s in range(0, api.comm_rounds, block)]
        steps = int(args[1].shape[2])
    else:
        fn, args, donate = progs["round"]
        sigs = [api.round_signature(r) for r in range(SIGNATURE_ROUNDS)]
        steps = int(args[1].shape[1])
    est = _mesh_round_estimate(api, args, steps=steps,
                               rounds_fused=max(1, block))
    # a fused block's census covers K rounds' collectives; scale the
    # per-round ObsCarry model to match
    modeled = {k: v * max(1, block)
               for k, v in _modeled_round_bytes(api, steps=steps).items()}
    s = int(getattr(api, "n_stage_shards", 1))
    shape = ((api.n_shards, s, api.n_model_shards) if s > 1
             else (api.n_shards, api.n_model_shards))
    return lower_program(
        name, fn, args, donate, mesh_shape=shape,
        expected_out=expected, modeled_bytes=modeled,
        estimate_bytes=est, signatures=sigs)


@registry.register("mesh1d_replicated", "mesh", "round")
def build_mesh1d_replicated() -> ProgramReport:
    """8-shard 1-D mesh, replicated merge (per-leaf psum all-reduce)."""
    return _build_mesh("mesh1d_replicated", "8,1", "replicated")


@registry.register("mesh1d_scatter", "mesh", "round", quick=True)
def build_mesh1d_scatter() -> ProgramReport:
    """8-shard 1-D mesh, reduce-scatter merge + shard-resident FedOpt
    moments (the arXiv:2004.13336 cross-replica layout)."""
    return _build_mesh("mesh1d_scatter", "8,1", "scatter", alg="FedOpt")


@registry.register("mesh2d_replicated", "mesh", "round")
def build_mesh2d_replicated() -> ProgramReport:
    """(4,2) client x model mesh, replicated merge — the GSPMD partial-
    auto shard_map layout (docs/MESH_2D.md)."""
    return _build_mesh("mesh2d_replicated", "4,2", "replicated")


@registry.register("mesh2d_scatter", "mesh", "round")
def build_mesh2d_scatter() -> ProgramReport:
    """(4,2) client x model mesh, scatter merge: flat server state over
    BOTH axes — the layout the PR 6 re-replication bug hit."""
    return _build_mesh("mesh2d_scatter", "4,2", "scatter", alg="FedOpt")


@registry.register("mesh_block8", "mesh", "block")
def build_mesh_block8() -> ProgramReport:
    """Fused round_block=8 scan on the 8-shard scatter mesh with the
    SCAFFOLD client table threading the donated carry."""
    return _build_mesh("mesh_block8", "8,1", "scatter", alg="SCAFFOLD",
                       block=8)


#: 3-D pipeline canonical config (docs/PIPELINE.md): pipe_mlp's stacked
#: blocks split 4 layers over s=2 stages, rows over m=2; microbatches=2
_PIPE_OVER = dict(model="pipe_mlp", model_dim=16, model_layers=4,
                  microbatches=2)


@registry.register("mesh3d_scatter", "mesh", "round")
def build_mesh3d_scatter() -> ProgramReport:
    """(2,2,2) client x stage x model pipeline mesh, scatter merge +
    FedOpt moments over c*s*m: the microbatched-pipeline train phase
    (stage-ring collective-permutes) feeding the byte-identical client
    merge (docs/PIPELINE.md)."""
    return _build_mesh("mesh3d_scatter", "2,2,2", "scatter", alg="FedOpt",
                       **_PIPE_OVER)


@registry.register("mesh3d_block8", "mesh", "block")
def build_mesh3d_block8() -> ProgramReport:
    """Fused round_block=8 scan on the (2,2,2) pipeline mesh with the
    SCAFFOLD client table — the fully-manual pipeline shard_map under the
    fused scan (docs/PIPELINE.md, docs/ROUND_FUSION.md)."""
    return _build_mesh("mesh3d_block8", "2,2,2", "scatter", alg="SCAFFOLD",
                       block=8, **_PIPE_OVER)


def _serving_paged_engine():
    import jax
    import jax.numpy as jnp
    from ..llm.model import LlamaConfig, LlamaLM
    from ..serving.batching import ContinuousBatchingEngine
    cfg = LlamaConfig(vocab_size=97, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=48,
                      dtype=jnp.float32, attn_impl="blockwise")
    model = LlamaLM(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    return ContinuousBatchingEngine(model, variables["params"], slots=4,
                                    buf_len=48, kv_page_tokens=8,
                                    prefill_chunk_tokens=16)


def _serving_paged_estimate(eng) -> float:
    import jax
    from ..core.memory_estimate import estimate_paged_serving_memory
    from ..core import tree as tree_util
    pool_leaves = jax.tree_util.tree_leaves(eng._pool)
    pool_bytes = sum(l.nbytes for l in pool_leaves)
    # transient gather window: pool[block_tables] per layer — price K+V
    # for ~2 live layers at the full per-slot window width
    per_page = max(l.nbytes / l.shape[0] for l in pool_leaves)
    window = 2 * 2 * eng.n_slots * eng.max_blocks * per_page
    return estimate_paged_serving_memory(
        n_params=tree_util.num_params(eng.raw_params), param_bytes=4,
        n_slots=eng.n_slots, pool_bytes=pool_bytes,
        block_table_bytes=float(eng._btabs.nbytes), window_bytes=window,
        vocab_size=97, horizon=eng.horizon)["total"]


def _build_serving_paged(which: str) -> ProgramReport:
    eng = _serving_paged_engine()
    try:
        est = _serving_paged_estimate(eng)
        progs = {n: (fn, args, donate)
                 for n, fn, args, donate in eng.step_programs()}
        fn, args, donate = progs[which]
        return lower_program(f"serving_paged_{which}", fn, args, donate,
                             mesh_shape=(1, 1), estimate_bytes=est)
    finally:
        eng.stop()


@registry.register("serving_paged_decode_step", "serving", "step")
def build_serving_paged_step() -> ProgramReport:
    """The paged engine's batched decode step: one shared page pool
    (DONATED — page moves are block-table data, never copies) addressed
    through traced per-slot block tables, horizon-scanned.  Pins the
    zero-steady-state-recompile memory plane of docs/SERVING.md."""
    return _build_serving_paged("decode_step")


@registry.register("serving_paged_prefill_chunk", "serving", "step",
                   quick=True)
def build_serving_paged_chunk() -> ProgramReport:
    """The paged engine's fixed-shape prefill chunk (donated pool,
    traced sample index): ONE program serves every chunk of every
    prompt — intermediate and final alike."""
    return _build_serving_paged("prefill_chunk")


#: name -> builder; the canonical verification surface, derived from the
#: first-class Program registry (``analysis/programs.py``, ISSUE 18) —
#: registration order is the report order everywhere (CLI, manifest,
#: bench --verify).
PROGRAMS = {p.name: p.build for p in registry.registered()}


def verify_programs(names: Optional[Sequence[str]] = None,
                    manifest_path: Optional[str] = None,
                    update: bool = False
                    ) -> Tuple[List[Finding], List[ProgramReport]]:
    """Build + lower + check the named programs (all by default).

    ``update=True`` rewrites the manifest's measured fields from these
    reports before checking, so a fresh manifest verifies clean and the
    git diff carries the contract change."""
    names = list(names) if names else list(PROGRAMS)
    unknown = [n for n in names if n not in PROGRAMS]
    if unknown:
        raise KeyError(f"unknown program(s) {unknown}; "
                       f"have {list(PROGRAMS)}")
    reports = [PROGRAMS[n]() for n in names]
    if update:
        update_manifest(reports, manifest_path)
    manifest = load_manifest(manifest_path)
    suppressions = manifest.get("suppressions", [])
    findings: List[Finding] = []
    for rep in reports:
        entry = manifest.get("programs", {}).get(rep.name)
        findings.extend(run_checks(rep, entry, suppressions))
    return findings, reports
