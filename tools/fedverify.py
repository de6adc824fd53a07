#!/usr/bin/env python
"""fedverify CLI — AOT lowering-level contract checks over the canonical
program registry (sharding, collective census, donation, HBM fit,
recompile surface; docs/FEDVERIFY.md).

Usage:
    python tools/fedverify.py                          # verify everything
    python tools/fedverify.py --programs mesh1d_scatter,mesh_block8
    python tools/fedverify.py --json                   # machine output
    python tools/fedverify.py --update-manifest        # refresh census
    python tools/fedverify.py --list-programs
    python tools/fedverify.py --list-rules

Exit codes mirror fedlint: 0 = no unsuppressed errors, 1 = at least one
(or any unsuppressed finding with --strict), 2 = usage error.

Unlike ``tools/fedlint.py`` (pure stdlib) this CLI lowers real programs,
so it needs jax + the package; it forces the 8-virtual-device CPU host
platform up front so every mesh program compiles hermetically on any
machine — no TPU required (the whole point: these contracts gate in CI).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _force_cpu_mesh():
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fedverify", description="AOT lowering-level contract "
        "checks (sharding, collectives, donation, HBM, recompiles)")
    ap.add_argument("--programs", default=None,
                    help="comma-separated subset of registered programs")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings + census as JSON")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on warnings too")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="include suppressed findings in human output")
    ap.add_argument("--manifest", default=None,
                    help="contracts.json path (default: "
                         "tests/data/fedverify/contracts.json)")
    ap.add_argument("--update-manifest", action="store_true",
                    help="rewrite the manifest's measured census fields "
                         "from this run (budgets/bands/suppressions are "
                         "preserved); the git diff is the review surface")
    ap.add_argument("--list-programs", action="store_true",
                    help="print the program registry and exit")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the contract-rule catalog and exit")
    args = ap.parse_args(argv)

    _force_cpu_mesh()
    from fedml_tpu.analysis import fedverify as fv

    if args.list_rules:
        for r in fv.VERIFY_RULES.values():
            print(f"{r.name:24s} [{r.severity}] {r.doc}")
        return 0
    if args.list_programs:
        for name, builder in fv.PROGRAMS.items():
            doc = (builder.__doc__ or "").split("\n")[0].strip()
            print(f"{name:24s} {doc}")
        return 0

    names = None
    if args.programs:
        names = [n.strip() for n in args.programs.split(",") if n.strip()]
        unknown = set(names) - set(fv.PROGRAMS)
        if unknown:
            print(f"fedverify: unknown program(s): "
                  f"{', '.join(sorted(unknown))}", file=sys.stderr)
            return 2

    findings, reports = fv.verify_programs(
        names, manifest_path=args.manifest, update=args.update_manifest)

    if args.as_json:
        print(json.dumps({
            "findings": json.loads(fv.findings_to_json(findings)),
            "census": {r.name: r.to_manifest_entry() for r in reports},
        }, indent=2))
    else:
        print(fv.render_findings(findings,
                                 show_suppressed=args.show_suppressed,
                                 tool="fedverify"))
    return fv.exit_code(findings, strict=args.strict)


if __name__ == "__main__":
    sys.exit(main())
