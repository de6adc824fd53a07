"""Process-parallel pytest sharding (pytest-xdist is not in the image).

Partitions the test FILES across N worker processes (greedy longest-
processing-time bin packing over the duration hints below) and runs one
pytest per shard concurrently.  File granularity keeps every existing
module-scoped fixture/process assumption intact — tests within a file never
split across workers.  This parent never imports jax; each shard is a
pytest process of its own, on the CPU platform tests/conftest.py sets.

Duration hints come from a full-suite run (2026-07-31, 296 tests, 47 min
contended / ~25 min solo); unknown files get a middle weight.  Exact values
only affect balance, not correctness.

Usage: python tools/pytest_shard.py [-n 4] [-m "not slow"] [extra pytest args]
Exit code: max of the shard exit codes (0 only if every shard passed).
"""

from __future__ import annotations

import argparse
import glob
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# rough seconds per file, solo-run scale; balance hints only
WEIGHTS = {
    "test_llm.py": 420, "test_mesh.py": 260, "test_serving_plane.py": 240,
    "test_algorithms.py": 220, "test_e2e_sp.py": 160, "test_moe.py": 150,
    "test_cross_silo.py": 150, "test_deploy_plane.py": 140,
    "test_speculative.py": 130, "test_flash_bwd.py": 120,
    "test_datasets_ext.py": 120, "test_scheduler.py": 110,
    "test_hierarchical_dcn.py": 110, "test_quantization.py": 100,
    "test_trust_stack.py": 100, "test_process_federation.py": 90,
    "test_secagg_cross_silo.py": 90, "test_native_edge.py": 90,
    "test_pipeline.py": 80, "test_compression.py": 80, "test_xent.py": 70,
    "test_mini_mqtt.py": 70, "test_hf_import.py": 60, "test_comm_ext.py": 60,
}
DEFAULT_WEIGHT = 50


def partition(files, n):
    """Greedy LPT bin packing, fully deterministic: ties in weight break on
    the basename, ties in load break on the lowest shard index, so the same
    file set always yields the same shards regardless of input order
    (glob order is filesystem-dependent) or `-p no:randomly`."""
    shards = [[] for _ in range(n)]
    loads = [0.0] * n
    for f in sorted(files, key=lambda f: (-WEIGHTS.get(os.path.basename(f),
                                                       DEFAULT_WEIGHT),
                                          os.path.basename(f))):
        i = loads.index(min(loads))
        shards[i].append(f)
        loads[i] += WEIGHTS.get(os.path.basename(f), DEFAULT_WEIGHT)
    return [s for s in shards if s]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", type=int, default=min(4, os.cpu_count() or 1),
                    help="worker processes (default: min(4, cores) — "
                         "oversubscribing cores just adds contention and "
                         "flakes timing-sensitive daemon tests)")
    ap.add_argument("-m", default=None, help="pytest -m marker expression")
    ap.add_argument("rest", nargs=argparse.REMAINDER,
                    help="extra pytest args (after --)")
    args = ap.parse_args()

    files = sorted(glob.glob(os.path.join(REPO, "tests", "test_*.py")))
    shards = partition(files, args.n)
    base = [sys.executable, "-m", "pytest", "-q"]
    if args.m:
        base += ["-m", args.m]
    base += [a for a in args.rest if a != "--"]

    t0 = time.time()
    procs = [subprocess.Popen(base + shard, cwd=REPO) for shard in shards]
    rcs = [p.wait() for p in procs]
    print(f"[shard] {len(shards)} shards finished in "
          f"{time.time() - t0:.0f}s, rcs={rcs}", flush=True)
    # pytest exit 5 = "no tests collected" (a shard whose files were all
    # deselected by -m) — fine per shard, but if EVERY shard collected
    # nothing (e.g. a typo'd -m expression) the run executed zero tests
    # and must not report success
    if all(rc == 5 for rc in rcs):
        print("[shard] ERROR: no tests collected in ANY shard "
              "(check the -m/-k expression)", flush=True)
        sys.exit(5)
    sys.exit(max((0 if rc == 5 else rc) for rc in rcs))


if __name__ == "__main__":
    main()
